"""Oracle-driven conformance fuzzer for the mutable filtered index.

Each case drives a seeded-random op sequence — ``add``, ``delete``,
``rebalance``, ``reboost``, ``delta-apply``, and filtered / lexical /
hybrid searches — over a randomly drawn ``top x bottom`` combo (or the
raw brute backend), and checks every search against a pure-numpy oracle
that mirrors the backend's *snapshot* state: the oracle advances only
at apply steps, exactly like the device arrays, so searches issued
between a mutation and its republish are checked against what the
backend actually serves, not the drifting host index.

Contract per search:

  * ids are unique, in-range for the snapshot, and every returned id
    satisfies the filter predicate AND the snapshot liveness mask — a
    tombstone applied in any earlier republish can never resurface;
  * an unsatisfiable predicate yields the full ``(inf, -1)`` sentinel
    surface with no NaNs;
  * the raw brute backend and the ivf kind (full probe scans every
    bucket) return *exactly* the oracle's top-k id set; the forest kind
    (approximate beam) must clear a calibrated recall floor;
  * lexical / hybrid answers on the raw backend match the BM25 oracle
    computed over snapshot slabs.

Failures re-raise with the reproduction seed (``proptest.run_cases``)
plus the tail of the op trace, so any violation replays exactly.

The fast suite spends ``FAST_STEPS`` total op-steps; the ``slow``
marker buys a deeper sweep of the same property.
"""
import jax
import numpy as np
import pytest

from proptest import run_cases
from repro.core.delta import DeltaManifest
from repro.core.lexical import bm25_dists, build_lexical_slabs, query_operands
from repro.core.metadata import FilterSpec, MetadataTable
from repro.core.two_level import (
    BOTTOM_ALGOS,
    TOP_ALGOS,
    TwoLevelConfig,
    build_two_level,
)
from repro.distributed.backend import ShardedSearchBackend
from repro.launch.mesh import make_mesh

N0, D, K, CAP, TOPK = 400, 8, 12, 80, 8
HEADROOM = 1.6
MAX_ROWS = int(N0 * 1.4)          # stay under the placed device capacity
FAST_STEPS = 200                  # total op-steps across the fast cases
SLOW_STEPS = 600

_MESH = None


def _mesh():
    global _MESH
    if _MESH is None:
        _MESH = make_mesh((1,), ("data",))
    return _MESH


def _corpus(rng, n):
    c = rng.normal(size=(8, D)) * 4
    return (c[rng.integers(0, 8, n)]
            + rng.normal(size=(n, D))).astype(np.float32)


def _draw_spec(case):
    """Random predicate over the ``pct`` column (None = unfiltered;
    ``eq 777`` is unsatisfiable — the selectivity-0 probe)."""
    r = case.int_(0, 6)
    if r == 0:
        return None
    if r == 1:
        return FilterSpec.eq("pct", 777)
    if r == 2:
        return FilterSpec.eq("pct", case.int_(0, 100))
    if r == 3:
        lo = case.int_(0, 95)
        return FilterSpec.range("pct", lo, lo + case.int_(0, 40))
    if r == 4:
        return FilterSpec.isin(
            "pct", case.rng.choice(100, size=7, replace=False))
    return (FilterSpec.range("pct", 0, 60)
            & FilterSpec.isin("pct", case.rng.choice(61, size=9,
                                                     replace=False)))


def _oracle_topk(q, db, ok, k):
    d = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    d = np.where(ok[None, :], d, np.inf)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    dd = np.take_along_axis(d, idx, 1)
    return dd, np.where(np.isinf(dd), -1, idx)


def _check_search(tag, trace, d, i, ok, exact, snap_db, q):
    """The per-search contract vs the snapshot oracle."""
    ctx = f"[{tag}] trace tail: {trace[-6:]}"
    snap_n = ok.shape[0]
    assert not np.isnan(d).any(), f"NaN distances {ctx}"
    real = i[i >= 0]
    assert (real < snap_n).all(), f"id beyond snapshot {ctx}"
    for row in i:
        r = row[row >= 0]
        assert len(set(r.tolist())) == len(r), f"duplicate ids {ctx}"
    assert ok[real].all(), (
        f"returned id violates filter/tombstone {ctx}")
    n_ok = int(ok.sum())
    if n_ok == 0:
        assert np.all(i == -1) and np.all(np.isinf(d)), (
            f"unsatisfiable predicate not the sentinel surface {ctx}")
        return
    od, oi = _oracle_topk(q, snap_db, ok, TOPK)
    if exact:
        for r in range(i.shape[0]):
            assert set(i[r].tolist()) == set(oi[r].tolist()), (
                f"exact backend diverged from oracle row {r}: "
                f"{i[r]} vs {oi[r]} {ctx}")
    elif n_ok >= 3 * TOPK:
        hits = sum(len(set(i[r][i[r] >= 0].tolist())
                       & set(oi[r][oi[r] >= 0].tolist()))
                   for r in range(i.shape[0]))
        want = sum(int((oi[r] >= 0).sum()) for r in range(i.shape[0]))
        rec = hits / max(1, want)
        assert rec >= 0.2, (
            f"forest recall {rec:.3f} under the calibrated floor "
            f"(n_ok={n_ok}) {ctx}")


# ---------------------------------------------------------------------------
# flavor 1: a random top x bottom combo through the delta/republish cycle
# ---------------------------------------------------------------------------


def _fuzz_two_level(case, n_steps):
    rng = case.rng
    top = case.choice(TOP_ALGOS)
    bottom = case.choice(BOTTOM_ALGOS)
    db = _corpus(rng, N0)
    host_db = db.copy()
    meta = MetadataTable(
        {"pct": (rng.permutation(N0) % 100).astype(np.int32)})
    p = rng.dirichlet(np.full(N0, 0.5)) if bottom == "qlbt" else None
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=K, top=top, bottom=bottom, kmeans_iters=3,
        kmeans_minibatch=None, bucket_cap=CAP, tree_leaf=4,
        lsh_bits=32, pq_m=4), p=p, metadata=meta)
    be = ShardedSearchBackend(
        _mesh(), idx, k=TOPK, axes=("data",), nprobe_local=K,
        beam_width=8, headroom=HEADROOM)
    exact = be.kind == "ivf"          # full probe scans every bucket
    tag = f"{top}/{bottom} seed={case.seed}"

    snap = dict(db=host_db.copy(),
                alive=np.ones(N0, bool),
                meta=meta.snapshot())
    trace = []
    for _ in range(n_steps):
        op = case.choice(["search", "search", "search", "search",
                          "add", "delete", "apply", "apply",
                          "rebalance", "reboost"])
        trace.append(op)
        if op == "add":
            m = case.int_(1, 9)
            if host_db.shape[0] + m > MAX_ROWS:
                continue
            new = _corpus(rng, m)
            idx.add_entities(new, metadata={
                "pct": rng.integers(0, 100, m).astype(np.int32)})
            host_db = np.concatenate([host_db, new])
        elif op == "delete":
            alive_now = (np.ones(idx.n, bool) if idx.alive is None
                         else np.asarray(idx.alive, bool))
            live = np.flatnonzero(alive_now)
            if live.size <= 4 * TOPK:
                continue
            dele = rng.choice(live, size=case.int_(1, 8), replace=False)
            idx.delete_entities(dele)
        elif op == "rebalance":
            idx.rebalance()
        elif op == "reboost":
            idx.reboost(rng.dirichlet(np.full(idx.n, 0.5)))
        elif op == "apply":
            man = idx.pop_delta()
            be.apply_updates(idx, delta=man)
            snap = dict(
                db=host_db.copy(),
                alive=(np.ones(idx.n, bool) if idx.alive is None
                       else np.asarray(idx.alive, bool).copy()),
                meta=meta.snapshot())
            trace[-1] = f"apply(v{man.version})"
        else:
            q = _corpus(rng, 4)
            fs = _draw_spec(case)
            d, i = be(q, filter_spec=fs)
            ok = (FilterSpec() if fs is None else fs).mask(
                snap["meta"], snap["db"].shape[0]) & snap["alive"]
            _check_search(tag, trace, d, i, ok, exact, snap["db"], q)


# ---------------------------------------------------------------------------
# flavor 2: the raw brute backend — exact everywhere, plus lexical/hybrid
# ---------------------------------------------------------------------------


def _fuzz_raw_brute(case, n_steps):
    rng = case.rng
    nv = 60
    db = _corpus(rng, N0)
    host_db = db.copy()
    meta = MetadataTable(
        {"pct": (rng.permutation(N0) % 100).astype(np.int32)})
    docs = [list(rng.integers(0, nv, rng.integers(3, 10)))
            for _ in range(N0)]
    slabs = build_lexical_slabs(docs, nv)
    be = ShardedSearchBackend(
        _mesh(), db, k=TOPK, axes=("data",), headroom=HEADROOM,
        metadata=meta, lexical=slabs, delta_max_fraction=1.0)
    tag = f"raw-brute seed={case.seed}"

    snap = dict(db=host_db.copy(), alive=np.ones(N0, bool),
                meta=meta.snapshot(), terms=slabs.terms.copy(),
                tf=slabs.tf_sat.copy())
    version = 0
    base_n = N0
    pending_tombs: list = []
    alive_host = np.ones(N0, bool)
    trace = []
    for _ in range(n_steps):
        op = case.choice(["search", "search", "search", "search",
                          "add", "delete", "apply", "apply"])
        trace.append(op)
        if op == "add":
            m = case.int_(1, 9)
            if host_db.shape[0] + m > MAX_ROWS:
                continue
            new = _corpus(rng, m)
            host_db = np.concatenate([host_db, new])
            alive_host = np.concatenate([alive_host, np.ones(m, bool)])
            slabs.append_docs(
                [list(rng.integers(0, nv, 6)) for _ in range(m)])
            meta.append_rows(
                {"pct": rng.integers(0, 100, m).astype(np.int32)}, m)
        elif op == "delete":
            live = np.flatnonzero(alive_host)
            if live.size <= 4 * TOPK:
                continue
            dele = rng.choice(live, size=case.int_(1, 8), replace=False)
            alive_host[dele] = False
            pending_tombs.extend(int(x) for x in dele)
        elif op == "apply":
            man = DeltaManifest(
                base_version=version, version=version + 1,
                base_n=base_n, n=host_db.shape[0],
                tombstones=np.asarray(sorted(pending_tombs), np.int64))
            be.apply_updates(host_db, delta=man)
            version += 1
            base_n = host_db.shape[0]
            pending_tombs = []
            snap = dict(db=host_db.copy(), alive=alive_host.copy(),
                        meta=meta.snapshot(), terms=slabs.terms.copy(),
                        tf=slabs.tf_sat.copy())
            trace[-1] = f"apply(v{version})"
        else:
            q = _corpus(rng, 3)
            fs = _draw_spec(case)
            mode = case.choice(["semantic", "semantic", "lexical",
                                "hybrid"])
            ok = (FilterSpec() if fs is None else fs).mask(
                snap["meta"], snap["db"].shape[0]) & snap["alive"]
            if mode == "semantic":
                d, i = be(q, filter_spec=fs)
                _check_search(tag, trace, d, i, ok, True, snap["db"], q)
                continue
            qt, qw = query_operands(
                [list(rng.integers(0, nv, 5)) for _ in range(3)], slabs)
            alpha = float(case.floats(0.0, 1.0))
            kw = dict(filter_spec=fs, q_terms=qt, q_weights=qw)
            d, i = be(q, mode=mode, alpha=alpha, **kw)
            trace[-1] = f"search({mode})"
            bd = bm25_dists(snap["terms"], snap["tf"],
                            np.asarray(qt), np.asarray(qw))
            if mode == "lexical":
                comb = bd
            else:
                d2 = ((q[:, None, :] - snap["db"][None, :, :]) ** 2
                      ).sum(-1)
                comb = alpha * d2 + (1.0 - alpha) * bd
            comb = np.where(ok[None, :], comb, np.inf)
            order = np.argsort(comb, axis=1, kind="stable")[:, :TOPK]
            od = np.take_along_axis(comb, order, 1)
            ctx = f"[{tag}] trace tail: {trace[-6:]}"
            assert not np.isnan(d).any(), f"NaN distances {ctx}"
            real = i[i >= 0]
            assert ok[real].all(), (
                f"{mode} returned id violating filter/tombstone {ctx}")
            if int(ok.sum()) == 0:
                assert np.all(i == -1) and np.all(np.isinf(d)), (
                    f"{mode}: unsatisfiable predicate not the sentinel "
                    f"surface {ctx}")
            else:
                fin = np.isfinite(od)
                np.testing.assert_allclose(
                    d[fin], od[fin], rtol=1e-4, atol=1e-4,
                    err_msg=f"{mode} distances diverged {ctx}")


# ---------------------------------------------------------------------------
# suites
# ---------------------------------------------------------------------------


def test_fuzz_two_level_fast():
    # 4 cases x 30 steps = 120 of the 200 fast-suite op-steps
    run_cases(_fuzz_two_level, n_cases=4, base_seed=41,
              n_steps=FAST_STEPS * 3 // 10 // 2)


def test_fuzz_raw_brute_fast():
    # 2 cases x 40 steps = the remaining 80 fast-suite op-steps
    run_cases(_fuzz_raw_brute, n_cases=2, base_seed=43,
              n_steps=FAST_STEPS // 5)


@pytest.mark.slow
def test_fuzz_two_level_deep():
    run_cases(_fuzz_two_level, n_cases=6, base_seed=47,
              n_steps=SLOW_STEPS * 3 // 5 // 6)


@pytest.mark.slow
def test_fuzz_raw_brute_deep():
    run_cases(_fuzz_raw_brute, n_cases=2, base_seed=53,
              n_steps=SLOW_STEPS // 5)
