"""The reduction from a profiler trace to busy, idle, op and gap numbers."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import devtrace  # noqa: E402

MS = 1e6  # ns


def _planes():
    """A window of 100 ms on one device: ops busy 0-10, 20-30 (two
    overlapping ops) and 60-70 ms; the host opens a request span over
    0-50 ms and a transfer over 40-50 ms."""
    host = ("/host:CPU", [
        ("python", [(devtrace.WINDOW, 0.0, 100 * MS),
                    ("client.search", 0.0, 50 * MS),
                    ("TransferToDevice", 40 * MS, 10 * MS)]),
    ])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_search(1)", 0.0, 10 * MS),
                         ("jit_search(1)", 20 * MS, 10 * MS),
                         ("jit_search(1)", 60 * MS, 10 * MS)]),
        ("XLA Ops", [("fusion.1", 0.0, 6 * MS),
                     ("candidate_topk.2", 6 * MS, 4 * MS),
                     ("fusion.7", 20 * MS, 8 * MS),
                     ("copy.3.1", 25 * MS, 5 * MS),
                     ("fusion.12", 60 * MS, 10 * MS),
                     ("fusion.13", 150 * MS, 10 * MS)]),   # after window
    ])
    return [host, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    r = devtrace.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.03)
    assert r["modules_s"] == pytest.approx(0.03)


def test_ops_group_by_name_without_numeric_suffixes():
    r = devtrace.reduce(_planes())
    ops = dict(r["ops"])
    assert ops["fusion"] == pytest.approx(0.024)
    assert ops["candidate_topk"] == pytest.approx(0.004)
    assert ops["copy"] == pytest.approx(0.005)
    assert r["ops"][0][0] == "fusion"
    assert devtrace.op_group("jit_search") == "jit_search"


def test_hlo_text_op_names_group_by_op_and_result_type():
    g = devtrace.op_group
    assert g("%copy.25 = f32[8192,306,128]{2,1,0:T(8,128)} copy(f32[8192,"
             "306,128]{2,0,1:T(8,128)} %bucket_vecs.1)") == \
        "copy f32[8192,306,128]"
    assert g("%copy.16 = f32[8192,306,128]{2,1,0:T(8,128)} copy(%x)") == \
        "copy f32[8192,306,128]"
    assert g("%while.2 = (s32[]{:T(128)}, f32[32,10]{1,0}) while(%t)") == \
        "while"
    assert g("%candidate_topk_pallas.6 = (f32[48,10]{1,0}, s32[48,10]{1,0}"
             ") custom-call(%a)") == "candidate_topk_pallas"


def test_idle_gaps_are_named_by_the_innermost_host_event():
    r = devtrace.reduce(_planes(), n_gaps=3)
    assert [round(s, 9) for _, s in r["gaps"]] == [0.03, 0.03, 0.01]
    # 30-60 ms: at 45 ms the transfer (40-50) is innermost inside the
    # request span; 70-100 ms: nothing open; 10-20 ms: the request
    assert sorted(name for name, _ in r["gaps"]) == [
        "TransferToDevice", "client.search", "no host event"]
    assert devtrace.reduce(_planes(), n_gaps=1)["gaps"][0][1] == \
        pytest.approx(0.03)


def test_gaps_sum_with_busy_to_the_window():
    r = devtrace.reduce(_planes(), n_gaps=100)
    idle = sum(s for _, s in r["gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_no_window_or_no_device_reads_nothing():
    host, dev = _planes()
    assert devtrace.reduce([host]) is None
    assert devtrace.reduce([("/host:CPU", [("python", [])]), dev]) is None


def test_several_devices_average_busy_and_sum_programs():
    host, dev = _planes()
    dev1 = ("/device:TPU:1", [("XLA Ops", [("fusion.1", 0.0, 50 * MS)]),
                              ("XLA Modules", [("m", 0.0, 50 * MS)])])
    r = devtrace.reduce([host, dev, dev1])
    assert r["busy_s"] == pytest.approx((0.03 + 0.05) / 2)
    assert r["modules_s"] == pytest.approx(0.08)


def test_a_recorded_trace_loads_into_planes_with_the_window():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with devtrace.recording(True) as rec:
        f(x).block_until_ready()
    planes = rec["planes"]
    assert any(name.startswith("/host:") for name, _ in planes)
    lo, hi = devtrace.window_ns(planes)
    assert hi > lo
    # the CPU has no device plane: nothing to reduce, and no error
    assert devtrace.reduce(planes) is None
