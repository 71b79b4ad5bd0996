"""Compile the main path for a described TPU v5e, at the deployments' real
widths, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so it refuses here what the chip would
refuse: blocks that overflow VMEM, slices that break the ``(8, 128)``
tiling rule, programs that do not fit device memory.  Interpret-mode
tests cannot see any of these.  Each case is one compile (about two
seconds); nothing runs, so results and times are not checked here.

The served functions pick the Pallas kernel only when
``jax.default_backend() == "tpu"``; on this CPU host the test steers that
dispatch with ``monkeypatch`` so the compiled program is the one the chip
would run, and asserts the kernel (``tpu_custom_call``) is in it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import bm25, bucket_topk, l2_topk
from repro.kernels import ops as kernel_ops

K = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Make ``kernels.ops`` take its TPU branch (native Pallas kernel)."""
    monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    return text


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_l2_topk_sift_1m(one_chip, precision):
    q = _shape(one_chip, (64, 128))
    if precision == "f32":
        _compile(lambda q, x: l2_topk.l2_topk_pallas(q, x, K),
                 q, _shape(one_chip, (1 << 20, 128)))
    else:
        _compile(lambda q, x, s: l2_topk.l2_topk_int8_pallas(q, x, s, K),
                 q, _shape(one_chip, (1 << 20, 128), jnp.int8),
                 _shape(one_chip, (1 << 20,)))


@pytest.mark.parametrize("cap,d", [(312, 128), (312, 96), (763, 96)])
def test_candidate_topk_bucket_widths(one_chip, cap, d):
    """sift-1m bucket cap at d=128 and d=96, and the deep-10m cap."""
    _compile(lambda q, v, i, bd, bi: bucket_topk.candidate_topk_pallas(
                 q, v, i, K, best_d=bd, best_i=bi),
             _shape(one_chip, (64, d)), _shape(one_chip, (64, cap, d)),
             _shape(one_chip, (64, cap), jnp.int32),
             _shape(one_chip, (64, K)), _shape(one_chip, (64, K), jnp.int32))


@pytest.mark.parametrize("mode", ["bm25", "hybrid"])
def test_lexical_kernels_served_size(one_chip, mode):
    n, slots, t = 1 << 18, 64, 8
    qt = _shape(one_chip, (64, t), jnp.int32)
    qw = _shape(one_chip, (64, t))
    terms = _shape(one_chip, (n, slots), jnp.int32)
    tf = _shape(one_chip, (n, slots))
    if mode == "bm25":
        _compile(lambda *a: bm25.bm25_topk_pallas(*a, K), qt, qw, terms, tf)
    else:
        _compile(lambda *a: bm25.hybrid_topk_pallas(*a, K),
                 _shape(one_chip, (64, 128)), _shape(one_chip, (n, 128)),
                 qt, qw, terms, tf, _shape(one_chip, (1, 1)))


# ---------------------------------------------------------------------------
# served functions: the jitted shard_map programs ShardedSearchBackend runs
# ---------------------------------------------------------------------------


def _served(topo, n_dev, kind, n, d, *, n_buckets=0, cap=0, nprobe=32,
            precision="f32"):
    """Compile one served search for ``n_dev`` described chips."""
    from repro.distributed.sharding import (
        make_sharded_brute_fn, make_sharded_ivf_fn)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n_dev,), ("data",), devices=topo.devices)
    spec = lambda *dims: NamedSharding(mesh, P(*dims))
    q = _shape(spec(None, None), (64, d))
    if kind == "brute":
        rows = -(-n // n_dev)
        fn = make_sharded_brute_fn(mesh, ("data",), K, rows,
                                   precision=precision)
        valid = _shape(spec("data"), (rows * n_dev,), jnp.bool_)
        if precision == "int8":
            args = (_shape(spec("data", None), (rows * n_dev, d), jnp.int8),
                    _shape(spec("data"), (rows * n_dev,)), valid, q)
        else:
            args = (_shape(spec("data", None), (rows * n_dev, d)), valid, q)
    else:
        kp = -(-n_buckets // n_dev) * n_dev
        fn = make_sharded_ivf_fn(mesh, ("data",), K, -(-nprobe // n_dev),
                                 kp // n_dev, n_buckets)
        args = (_shape(spec("data", None), (kp, d)),
                _shape(spec("data", None), (kp, cap), jnp.int32),
                _shape(spec("data", None, None), (kp, cap, d)), q)
    return _compile(fn, *args)


@pytest.mark.parametrize("kind,precision", [
    ("ivf", "f32"), ("brute", "f32"), ("brute", "int8")])
def test_served_sift_1m_one_chip(topo, tpu_dispatch, kind, precision):
    _served(topo, 1, kind, 1_000_000, 128, n_buckets=8192, cap=312,
            precision=precision)


@pytest.mark.parametrize("kind", ["ivf", "brute"])
def test_served_deep_10m_four_chips(topo, tpu_dispatch, kind):
    _served(topo, 4, kind, 10_000_000, 96, n_buckets=32768, cap=763)


def _entry_param(text, i):
    """Name and entry layout of the compiled program's ``i``-th parameter."""
    head = text.splitlines()[0]
    layouts = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", re.search(
        r"entry_computation_layout=\{\((.*?)\)->", head).group(1))
    entry = re.search(r"^ENTRY %\S+ \((.*?)\) ->", text, re.M).group(1)
    names = re.findall(r"([\w.]+): \w+\[", entry)
    return names[i], layouts[i]


@pytest.mark.parametrize("n_dev,n_buckets,width,d,layout", [
    (1, 8192, 306, 128, "{2,1,0:"),    # sift-1m on one chip
    # deep-10m on four: at d = 96 the compiler keeps the bucket width in
    # lanes, and the probe loop reads the tensor in that layout as placed
    (4, 32768, 763, 96, "{1,2,0:"),
])
def test_served_ivf_reserved_width_keeps_bucket_layout(
        topo, tpu_dispatch, n_dev, n_buckets, width, d, layout):
    """At the width the backend reserves for an unaligned index width, the
    bucket-vector tensor enters the program in the layout its probe loop
    reads: no per-call copy of the whole tensor."""
    from repro.distributed.sharding import _bucket_width

    cap = _bucket_width(width)
    text = _served(topo, n_dev, "ivf", 0, d, n_buckets=n_buckets, cap=cap)
    name, entry_layout = _entry_param(text, 2)
    assert entry_layout.startswith(f"f32[{-(-n_buckets // n_dev)},{cap},{d}]")
    assert f"copy(%{name})" not in text, "the bucket tensor is re-laid out"
    assert layout in entry_layout, entry_layout
