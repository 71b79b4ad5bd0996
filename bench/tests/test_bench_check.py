"""The comparison that decides ``correct``: sound runs pass it, the
control and a broken timed path fail it."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent),
                str(ROOT / "bench")]

from bench import harness, reference  # noqa: E402
from tiny import make_root  # noqa: E402

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell):
    return harness.run(cell, SEED, 0.5, False, root=root,
                       require_chip=False, compile_cache=False,
                       log=lambda _: None)


@pytest.mark.parametrize("cell", ["tiny-ivf.tiny-open",
                                  "tiny-qlbt.tiny-closed"])
def test_a_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"] is True
    assert out["failed"] == 0


def _break_backend(monkeypatch, how):
    """Plant a fault where the timed path produces its answers."""
    from bench import data
    from repro.distributed.backend import ShardedSearchBackend

    call = ShardedSearchBackend.__call__
    corpus = data.corpus
    seen = {}

    def recording(spec, seed):
        seen["db"], std = corpus(spec, seed)
        return seen["db"], std

    monkeypatch.setattr(data, "corpus", recording)

    def broken(self, queries, **kw):
        d, i = call(self, queries, **kw)
        d, i = d.copy(), i.copy()
        if how == "altered":
            # each answer's ids point one row off what was scored
            i = np.where(i >= 0, (i + 1) % self._n, i)
        elif how == "rest":
            # the nearest row kept, the rows behind it one row on, scored
            # exactly and re-ranked: well-formed answers, wrong rows
            q = np.asarray(queries, np.float64)[:, None, :]
            rest = (i[:, 1:] + 1) % self._n
            dd = np.sum((seen["db"][rest].astype(np.float64) - q) ** 2, -1)
            order = np.argsort(dd, axis=1)
            i[:, 1:] = np.take_along_axis(rest, order, axis=1)
            d[:, 1:] = np.maximum(np.take_along_axis(dd, order, axis=1),
                                  d[:, :1])
        else:
            # half of the batch left out: its rows get the other half's
            h = (queries.shape[0] + 1) // 2
            d[h:], i[h:] = d[:queries.shape[0] - h], i[:queries.shape[0] - h]
        return d, i

    monkeypatch.setattr(ShardedSearchBackend, "__call__", broken)


@pytest.mark.parametrize("how", ["altered", "half_batch"])
@pytest.mark.parametrize("cell", ["tiny-ivf.tiny-closed",
                                  "tiny-qlbt.tiny-closed"])
def test_a_broken_timed_path_is_not_correct(root, cell, how, monkeypatch):
    _break_backend(monkeypatch, how)
    assert _run(root, cell)["correct"] is False


@pytest.mark.parametrize("cell", ["tiny-ivf.tiny-closed",
                                  "tiny-qlbt.tiny-closed"])
def test_wrong_rows_behind_the_nearest_fail_rest_miss(root, cell,
                                                      monkeypatch):
    """Every first row right and every distance exact, so only the rows
    behind the first can give the fault away."""
    _break_backend(monkeypatch, "rest")
    out = _run(root, cell)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["rest_miss"]["value"] > checks["rest_miss"]["limit"]
    assert checks["top1_miss"]["value"] <= checks["top1_miss"]["limit"]


@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
@pytest.mark.parametrize("config", ["sift-1m-ivf", "radio-station-qlbt"])
def test_the_control_fails_the_configurations_limits(config, seed,
                                                     tmp_path):
    """The reference in bfloat16 in the program's place, at a size a
    test holds, against each configuration's own limits."""
    import control

    real = json.loads((ROOT / "bench/configs" / f"{config}.json")
                      .read_text())
    root = make_root(tmp_path)
    tiny = "tiny-ivf" if config == "sift-1m-ivf" else "tiny-qlbt"
    path = root / "bench/configs" / f"{tiny}.json"
    cfg = json.loads(path.read_text())
    cfg["corpus"] = dict(real["corpus"], n=4096,
                         mixture_clusters=real["corpus"]["mixture_clusters"]
                         // 16)
    cfg["check"] = real["check"]
    path.write_text(json.dumps(cfg))
    cell = harness.load_cell(f"{tiny}.tiny-open", root)
    numbers = control.control_numbers(cell, seed)
    assert numbers["malformed"] == 0
    assert not reference.verdict(numbers, real["check"]["limits"])
    assert numbers["dist_err"] > real["check"]["limits"]["dist_err"]


def test_answer_checks_flag_every_malformed_answer():
    rng = np.random.default_rng(0)
    db = rng.normal(size=(50, 8)).astype(np.float32)
    q = db[:4] + 0.01
    ids, dists = reference.exact_topk(db, q, 3)
    assert reference.answer_checks(db, q, dists, ids)[0] == 0
    bad_i, bad_d = ids.copy(), dists.copy()
    bad_i[0, 1] = bad_i[0, 0]            # repeated id
    bad_i[1, 2] = 50                     # out of range
    bad_d[2] = bad_d[2][::-1]            # out of order
    bad_d[3, 0] = np.nan                 # not finite
    assert reference.answer_checks(db, q, bad_d, bad_i)[0] == 4
    off = dists.copy()
    off[0, 2] += 0.5                     # sorted still, but wrong
    assert reference.answer_checks(db, q, off, ids)[0] == 0
    assert reference.answer_checks(db, q, off, ids)[1] > 1e-3


def test_sample_checks_count_misses_against_the_exact_answer():
    truth = np.array([[1, 2, 3], [4, 5, 6]])
    got = np.array([[1, 3, 9], [5, 4, 6]])
    top1, miss = reference.sample_checks(got, truth)
    assert top1 == 0.5
    assert miss == pytest.approx(1 / 6)
    # ranks 2..k against a deeper exact ranking: 3 and 9, 4 and 6
    deeper = np.array([[1, 2, 3, 4], [4, 5, 6, 7]])
    assert reference.rest_miss(got, deeper) == pytest.approx(1 / 4)


def test_a_number_that_is_not_finite_is_never_within_its_limit():
    assert not reference.verdict({"a": float("nan")}, {"a": 1.0})
    assert reference.verdict({"a": 0, "b": 0.5}, {"a": 0, "b": 0.5})
    assert not reference.verdict({"a": 1}, {"a": 0})
