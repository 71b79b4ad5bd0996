"""The search roofline's work count against shapes worked out by hand."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

roofline = spec.reader("search_roofline.steady")


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_ivf_work_is_centroids_per_dispatch_and_probed_rows_per_query():
    # sift-1m: 10 dispatches serving 400 queries; 8192 x 128 centroids,
    # 32 probed buckets of 1e6 / 8192 = 122.0703125 rows on average
    nbytes, flops = roofline.work(_config("sift-1m-ivf"), 10, 400)
    rows = 32 * 1e6 / 8192
    assert nbytes == pytest.approx(10 * 8192 * 128 * 4
                                   + 400 * (rows + 1) * 128 * 4)
    assert nbytes == pytest.approx(842_147_840)
    assert flops == pytest.approx(400 * 2 * 128 * (8192 + rows))
    assert flops == pytest.approx(1_238_860_800)


def test_qlbt_work_reads_what_the_descent_reaches():
    # radio-station: 78.125 rows a bucket on average, but the descent
    # reranks beam 8 x leaf 8 = 64 of them in each of 8 probed buckets
    nbytes, flops = roofline.work(_config("radio-station-qlbt"), 10, 400)
    assert nbytes == pytest.approx(10 * 128 * 256 * 4
                                   + 400 * (8 * 64 + 1) * 256 * 4)
    assert flops == pytest.approx(400 * 2 * 256 * (128 + 8 * 64))


def test_roofline_share_names_its_bound():
    peaks = spec.peaks("TPU v5 lite")
    ctx = SimpleNamespace(config=_config("sift-1m-ivf"), batches=(10, 400),
                          device={"modules_s": 0.01}, peaks=peaks)
    least = 842_147_840 / 819e9
    assert roofline.read(ctx) == pytest.approx(100 * least / 0.01)
    assert "hbm-bound" in roofline.describe(ctx)
    # the float32 peak is the bf16 peak over six passes, and says so
    assert peaks["f32_highest_flops_per_s"] == pytest.approx(197e12 / 6)
    assert "derived" in peaks["f32_highest_flops_per_s_is"]


def test_nothing_to_read_gives_no_number():
    ctx = SimpleNamespace(config=_config("sift-1m-ivf"), batches=(0, 0),
                          device={"modules_s": 0.0},
                          peaks=spec.peaks("TPU v5 lite"))
    assert roofline.read(ctx) is None
    assert roofline.read(SimpleNamespace(device=None, peaks=None)) is None


def test_a_device_missing_from_the_peak_table_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks("TPU v9 imaginary")
