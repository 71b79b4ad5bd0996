"""Multi-device semantics on 8 fake devices (subprocess: tests themselves
run single-device).  Covers: distributed exact/IVF/forest search through
``ShardedSearchBackend``, query+corpus 2-axis sharding, the shared
gather-merge of the row-sharded scans, the serving engine, compressed
psum, elastic checkpoint resharding, and a sharded LM train step.

The subprocess tests are marked ``slow`` (each pays a fresh 8-device JAX
start-up); the in-process slicing tests run in the default CI job.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import REPO, subprocess_env

slow = pytest.mark.slow

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def _run(body: str):
    code = _PRELUDE + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=subprocess_env(), cwd=REPO,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# fast, in-process: the forest slicer
# ---------------------------------------------------------------------------


def test_query_axes_must_be_disjoint_from_corpus_axes():
    """A shared axis would top-k-merge results of *different* queries —
    refuse loudly instead of returning silently wrong neighbors."""
    from repro.distributed import ShardedSearchBackend
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    db = np.zeros((8, 4), np.float32)
    with pytest.raises(ValueError, match="disjoint"):
        ShardedSearchBackend(mesh, db, k=2, axes=("data",),
                             query_axes=("data",))


def test_shard_forest_slices_conserve_entities():
    """Slicing the concatenated forest into shards keeps every node and
    maps each leaf slot id back to the entity the global forest holds."""
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed import shard_forest

    rng = np.random.default_rng(0)
    db = rng.normal(size=(600, 8)).astype(np.float32)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=16, top="brute", bottom="tree", kmeans_iters=3,
        tree_leaf=4))
    n_dev = 4
    sh = shard_forest(idx, n_dev)
    K, cap = idx.bucket_ids.shape
    Kloc = -(-K // n_dev)
    # a real node is internal (children >= 0) or a leaf (leaf_row >= 0);
    # everything else is shard padding / the dead node
    total_nodes = sum(
        int(((sh["children"][s, :, 0] >= 0)
             | (sh["leaf_row"][s] >= 0)).sum())
        for s in range(n_dev))
    assert total_nodes == np.asarray(idx.forest.arrays["children"]).shape[0]
    seen = []
    for s in range(n_dev):
        assert sh["valid"][s].sum() == min(Kloc, max(0, K - s * Kloc))
        le = sh["leaf_entities"][s]
        slots = le[le >= 0]
        gids = sh["bucket_ids"][s].reshape(-1)[slots]
        assert (gids >= 0).all()      # every slot id resolves to an entity
        seen.append(gids)
    seen = np.concatenate(seen)
    # forests partition entities: each appears exactly once across shards
    assert np.array_equal(np.sort(seen), np.arange(db.shape[0]))


def test_shard_forest_shapes_stable_across_mutation():
    """Slicing a mutated forest into the shapes recorded before the
    mutation yields identically-shaped shards (the no-re-jit contract),
    and outgrowing the reservation raises instead of silently reshaping."""
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed import forest_shard_shapes, shard_forest

    rng = np.random.default_rng(1)
    db = rng.normal(size=(600, 8)).astype(np.float32)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=16, top="brute", bottom="tree", kmeans_iters=3,
        tree_leaf=4))
    n_dev = 4
    shapes = forest_shard_shapes(idx, n_dev, headroom=1.5)
    sh0 = shard_forest(idx, n_dev, shapes=shapes)
    idx.delete_entities(rng.choice(600, 150, replace=False))
    idx.add_entities(rng.normal(size=(180, 8)).astype(np.float32))
    idx.rebalance()
    sh1 = shard_forest(idx, n_dev, shapes=shapes)
    for name in sh0:
        if name == "max_depth":
            assert sh0[name] == sh1[name]
            continue
        assert sh0[name].shape == sh1[name].shape, name
    # shard contents track the mutation: no deleted slot survives
    le = sh1["leaf_entities"]
    slots = le[le >= 0]
    # every remaining slot resolves to a live entity
    for s in range(n_dev):
        les = sh1["leaf_entities"][s]
        gids = sh1["bucket_ids"][s].reshape(-1)[les[les >= 0]]
        assert (gids >= 0).all()
        assert idx.alive[gids].all()
    # tiny reservation -> loud failure, not silent reshape
    import dataclasses

    small = dataclasses.replace(
        forest_shard_shapes(idx, n_dev, headroom=1.0), nodes=2)
    with pytest.raises(ValueError, match="outgrew"):
        shard_forest(idx, n_dev, shapes=small)


def test_shard_forest_slab_layout_conserves_entities():
    """The slab layout (delta-shipping layout: fixed per-bucket node/leaf
    windows) must hold exactly the same entities as the packed layout —
    same contract as the packed slicer test, plus every bucket's nodes
    land inside its own slab."""
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed import forest_shard_shapes, shard_forest

    rng = np.random.default_rng(7)
    db = rng.normal(size=(600, 8)).astype(np.float32)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=16, top="brute", bottom="tree", kmeans_iters=3,
        tree_leaf=4))
    n_dev = 4
    shapes = forest_shard_shapes(idx, n_dev, headroom=1.0, layout="slab")
    assert shapes.node_slab > 0
    assert shapes.nodes == shapes.kloc * shapes.node_slab
    sh = shard_forest(idx, n_dev, shapes=shapes)
    seen = []
    for s in range(n_dev):
        le = sh["leaf_entities"][s]
        slots = le[le >= 0]
        gids = sh["bucket_ids"][s].reshape(-1)[slots]
        assert (gids >= 0).all()
        seen.append(gids)
        # every real root sits at its slot's slab start
        val = sh["valid"][s]
        for j in np.nonzero(val)[0]:
            assert sh["roots"][s, j] == j * shapes.node_slab
    seen = np.concatenate(seen)
    assert np.array_equal(np.sort(seen), np.arange(db.shape[0]))


def test_shard_forest_slab_shapes_stable_across_mutation():
    """Slab re-slicing of a mutated forest keeps identical shapes (the
    no-re-jit contract), and a bucket outgrowing its slab raises."""
    import dataclasses

    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed import forest_shard_shapes, shard_forest

    rng = np.random.default_rng(8)
    db = rng.normal(size=(600, 8)).astype(np.float32)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=16, top="brute", bottom="tree", kmeans_iters=3,
        tree_leaf=4))
    n_dev = 4
    shapes = forest_shard_shapes(idx, n_dev, headroom=1.5, layout="slab")
    sh0 = shard_forest(idx, n_dev, shapes=shapes)
    idx.delete_entities(rng.choice(600, 150, replace=False))
    idx.add_entities(rng.normal(size=(180, 8)).astype(np.float32))
    idx.rebalance()
    sh1 = shard_forest(idx, n_dev, shapes=shapes)
    for name in sh0:
        if name == "max_depth":
            continue
        assert sh0[name].shape == sh1[name].shape, name
    small = dataclasses.replace(shapes, node_slab=1)
    with pytest.raises(ValueError, match="outgrew"):
        shard_forest(idx, n_dev, shapes=small)


def test_slice_forest_delta_matches_full_slab_slice():
    """A dirty bucket's delta slab must be byte-identical to the same
    bucket's window in a full slab re-slice — the invariant that makes
    the device scatter equivalent to a full re-place."""
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed import (
        forest_shard_shapes,
        shard_forest,
        slice_forest_delta,
    )

    rng = np.random.default_rng(9)
    db = rng.normal(size=(600, 8)).astype(np.float32)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=16, top="brute", bottom="tree", kmeans_iters=3,
        tree_leaf=4))
    n_dev = 4
    shapes = forest_shard_shapes(idx, n_dev, headroom=1.5, layout="slab")
    b = int(np.argmax(idx.bucket_counts))
    idx.delete_entities(idx.bucket_ids[b][:4].copy())
    man = idx.pop_delta()
    pay = slice_forest_delta(idx, shapes, man.dirty_buckets)
    full = shard_forest(idx, n_dev, shapes=shapes)
    ns, ls = shapes.node_slab, shapes.leaf_slab
    for u in range(pay["shard"].size):
        s, j = int(pay["shard"][u]), int(pay["slot"][u])
        np.testing.assert_array_equal(
            pay["proj"][u], full["proj"][s, j * ns:(j + 1) * ns])
        np.testing.assert_array_equal(
            pay["children"][u], full["children"][s, j * ns:(j + 1) * ns])
        np.testing.assert_array_equal(
            pay["leaf_entities"][u],
            full["leaf_entities"][s, j * ls:(j + 1) * ls])
        np.testing.assert_array_equal(
            pay["bucket_ids"][u], full["bucket_ids"][s, j])
        np.testing.assert_array_equal(pay["bvecs"][u], full["bvecs"][s, j])
        assert pay["roots"][u] == full["roots"][s, j]


@pytest.mark.parametrize("width,headroom,dtype,want", [
    (306, 1.0, np.float32, 312),     # sift-1m's built width
    (312, 1.0, np.float32, 312),     # already on the tile
    (763, 1.0, np.float32, 768),     # deep-10m's built width
    (306, 1.5, np.float32, 464),     # headroom first, then the tile
    (306, 1.0, np.int32, 312),
    (306, 1.0, np.float16, 320),     # 16-bit data: a 16-row tile
    (306, 1.0, np.int8, 320),        # 8-bit data: a 32-row tile
])
def test_bucket_width_rounds_up_to_sublane_tile(width, headroom, dtype, want):
    from repro.distributed.sharding import _bucket_width

    assert _bucket_width(width, headroom, dtype) == want


def test_ivf_backend_reserves_tile_aligned_width():
    """An IVF index whose width is off the 8-row tile is placed at the
    rounded width: same answers as the width left as built, and a bucket
    delta ships payloads of the placed shapes."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed.backend import ShardedSearchBackend
    from repro.distributed.sharding import (
        _ivf_device_arrays,
        make_sharded_ivf_fn,
        slice_ivf_delta,
    )
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    c = rng.normal(size=(16, 16)) * 4

    def mk(n):
        return (c[rng.integers(0, 16, n)]
                + rng.normal(size=(n, 16))).astype(np.float32)

    idx = build_two_level(mk(1500), TwoLevelConfig(
        n_clusters=24, top="brute", bottom="brute", kmeans_iters=4))
    n_buckets, width = idx.bucket_ids.shape
    assert width % 8, "the case needs a width off the tile"
    cap = -(-width // 8) * 8
    mesh = make_mesh((1,), ("data",))
    be = ShardedSearchBackend(mesh, idx, kind="ivf", k=10, nprobe_local=6,
                              axes=("data",))
    assert be._cap == cap
    assert be._args[1].shape == (n_buckets, cap)
    assert be._args[2].shape == (n_buckets, cap, 16)
    assert be.metrics.get("ivf_bucket_width").value == cap
    assert be.metrics.get("ivf_bucket_pad_slots").value == cap - width

    q = mk(32)
    cents, bids, bvecs, kp = _ivf_device_arrays(idx, 1, cap=width)
    assert bvecs.shape == (n_buckets, width, 16)
    built = jax.jit(make_sharded_ivf_fn(mesh, ("data",), 10, 6, kp,
                                        n_buckets))
    put = lambda x, *spec: jax.device_put(x, NamedSharding(mesh, P(*spec)))
    with mesh:
        d0, i0 = jax.device_get(built(
            put(cents, "data", None), put(bids, "data", None),
            put(bvecs, "data", None, None), put(q, None, None)))
    d1, i1 = be(q)
    np.testing.assert_array_equal(i1, i0)
    np.testing.assert_array_equal(d1, d0)

    # grow buckets below the reserved width: a delta of the placed shapes
    idx.add_entities(mk(12))
    assert idx.bucket_ids.shape[1] <= cap
    man = idx.pop_delta()
    pay = slice_ivf_delta(idx, be._cap, man.dirty_buckets)
    assert pay["bucket_ids"].shape[1:] == be._args[1].shape[1:]
    assert pay["bvecs"].shape[1:] == be._args[2].shape[1:]
    st = be.apply_updates(idx, delta=man)
    assert st["mode"] == "delta"
    assert [a.shape for a in be._args] == [(n_buckets, 16), (n_buckets, cap),
                                           (n_buckets, cap, 16)]
    fresh = ShardedSearchBackend(mesh, idx, kind="ivf", k=10,
                                 nprobe_local=6, axes=("data",))
    for x, y in zip(be._args, fresh._args):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    d2, i2 = be(q)
    d3, i3 = fresh(q)
    np.testing.assert_array_equal(i2, i3)
    np.testing.assert_array_equal(d2, d3)


# ---------------------------------------------------------------------------
# slow, subprocess: real 8-device semantics
# ---------------------------------------------------------------------------


@slow
def test_sharded_brute_matches_exact():
    out = _run("""
    from repro.distributed import ShardedSearchBackend
    from repro.core.brute import brute_search
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    db = rng.normal(size=(3000, 16)).astype(np.float32)
    q = rng.normal(size=(32, 16)).astype(np.float32)
    d, i = ShardedSearchBackend(mesh, db, k=10)(q)
    dt, it = brute_search(q, db, 10)
    print("MATCH", float((np.asarray(i) == it).mean()))
    """)
    assert "MATCH 1.0" in out


@slow
def test_query_and_corpus_2axis_sharded_matches_exact():
    """Corpus sharded over one mesh axis, query batch over the other —
    results identical to the single-device scan (B not divisible by the
    query axis exercises the host-side batch pad)."""
    out = _run("""
    from repro.distributed import ShardedSearchBackend
    from repro.core.brute import brute_search
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(1)
    db = rng.normal(size=(2500, 16)).astype(np.float32)
    q = rng.normal(size=(37, 16)).astype(np.float32)   # 37 % 4 != 0
    d, i = ShardedSearchBackend(mesh, db, k=10, axes=("data",),
                                query_axes=("model",))(q)
    dt, it = brute_search(q, db, 10)
    print("MATCH", float((np.asarray(i) == it).mean()),
          float(np.abs(np.asarray(d) - dt).max()))
    """)
    assert "MATCH 1.0" in out


_SCANS = ("brute-f32", "brute-int8", "lexical", "hybrid")


@pytest.fixture(scope="module")
def gather_merge_rows():
    """One 8-device run of every row-sharded builder with ``k`` above
    both a shard's rows and the corpus: 13 rows over 8 shards (2 a
    shard), row 5 tombstoned, ``k`` 16.  Returns each scan's
    (dists, ids) and the exact f32 order of the dense corpus."""
    out = _run("""
    from repro.core.lexical import build_lexical_slabs, query_operands
    from repro.distributed import ShardedSearchBackend
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(11)
    db = rng.normal(size=(13, 16)).astype(np.float32)
    q = rng.normal(size=(3, 16)).astype(np.float32)
    slabs = build_lexical_slabs(
        [list(rng.integers(0, 20, 6)) for _ in range(13)], 20)
    qt, qw = query_operands(
        [list(rng.integers(0, 20, 4)) for _ in range(3)], slabs)
    alive = np.ones(13, bool)
    alive[5] = False
    kw = dict(k=16, alive=alive)
    be = ShardedSearchBackend(mesh, db, lexical=slabs, **kw)
    be8 = ShardedSearchBackend(mesh, db, precision="int8", **kw)
    res = {"brute-f32": be(q), "brute-int8": be8(q),
           "lexical": be(q, mode="lexical", q_terms=qt, q_weights=qw),
           "hybrid": be(q, mode="hybrid", alpha=0.5,
                        q_terms=qt, q_weights=qw)}
    d2 = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    d2[:, 5] = np.inf
    res["exact"] = (np.argsort(d2, axis=1, kind="stable")[:, :12], None)
    import json
    print("RESULT", json.dumps({name: [np.asarray(a).tolist()
                                       for a in r if a is not None]
                                for name, r in res.items()}))
    """)
    res = json.loads(out.split("RESULT", 1)[1])
    return {name: tuple(np.asarray(a) for a in r)
            for name, r in res.items()}


@slow
@pytest.mark.parametrize("scan", _SCANS)
def test_gather_merge_pads_k_above_shard_rows(gather_merge_rows, scan):
    """``k`` above a shard's rows: each shard returns its 2 rows, the
    shared gather-merge pads them to ``k`` with ``(inf, -1)`` and maps
    shard-local slots to global rows.  Every live row comes back exactly
    once, in ascending distance, and the tail is the sentinel."""
    d, i = gather_merge_rows[scan]
    assert d.shape == i.shape == (3, 16)
    live = [r for r in range(13) if r != 5]
    for b in range(3):
        assert sorted(i[b, :12].tolist()) == live, (scan, i[b])
        assert np.isfinite(d[b, :12]).all()
        assert (np.diff(d[b, :12]) >= 0).all()
        assert (i[b, 12:] == -1).all() and np.isinf(d[b, 12:]).all()
    if scan == "brute-f32":
        assert np.array_equal(i[:, :12], gather_merge_rows["exact"][0])


@slow
def test_sharded_ivf_recall():
    out = _run("""
    from repro.distributed import ShardedSearchBackend
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.core.brute import brute_search
    from repro.core.metrics import recall_at_k
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    c = rng.normal(size=(32, 16)) * 4
    db = (c[rng.integers(0, 32, 4000)] + rng.normal(size=(4000, 16))).astype(np.float32)
    q = db[:64] + rng.normal(size=(64, 16)).astype(np.float32) * 0.05
    idx = build_two_level(db, TwoLevelConfig(n_clusters=64, top="brute",
                          bottom="brute", kmeans_iters=5))
    d, i = ShardedSearchBackend(mesh, idx, k=10, nprobe_local=4)(q)
    _, it = brute_search(q, db, 10)
    print("RECALL", recall_at_k(np.asarray(i), it))
    """)
    recall = float(out.split("RECALL")[1].strip())
    assert recall > 0.8


@slow
@pytest.mark.parametrize("delta_updates", [True, False],
                         ids=["slab", "packed"])
def test_sharded_forest_recall(delta_updates):
    """Tree/QLBT forest bottom level, sharded: each chip descends its own
    slice of the concatenated forest; merged recall clears the paper bar
    in both forest layouts (``delta_updates`` picks slab or packed)."""
    out = _run(f"    delta_updates = {delta_updates}\n" + """
    from repro.distributed import ShardedSearchBackend
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.core.brute import brute_search
    from repro.core.metrics import recall_at_k
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    c = rng.normal(size=(32, 16)) * 4
    db = (c[rng.integers(0, 32, 4000)] + rng.normal(size=(4000, 16))).astype(np.float32)
    q = db[:64] + rng.normal(size=(64, 16)).astype(np.float32) * 0.05
    idx = build_two_level(db, TwoLevelConfig(n_clusters=64, top="brute",
                          bottom="tree", kmeans_iters=5, tree_leaf=8))
    d, i = ShardedSearchBackend(mesh, idx, k=10, nprobe_local=4,
                                beam_width=8,
                                delta_updates=delta_updates)(q)
    _, it = brute_search(q, db, 10)
    print("RECALL", recall_at_k(np.asarray(i), it))
    d2, i2 = ShardedSearchBackend(mesh, idx, k=10, nprobe_local=4,
                                  beam_width=8, axes=("data",),
                                  query_axes=("model",),
                                  delta_updates=delta_updates)(q)
    print("RECALL2", recall_at_k(np.asarray(i2), it))
    """)
    assert float(out.split("RECALL2")[1].strip()) > 0.8
    assert float(out.split("RECALL")[1].split()[0]) > 0.8


@slow
def test_sharded_ivf_full_probe_identical_to_single_device():
    """At full probe both paths are exact scans over the bucketed corpus,
    so the sharded IVF must return the *identical* (id, distance) sets as
    the unsharded index — including bucket-grid padding (K % shards != 0)
    and row padding (N % shards != 0), the PR 2 edge cases."""
    out = _run("""
    from repro.distributed import ShardedSearchBackend
    from repro.core.two_level import TwoLevelConfig, build_two_level
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(3)
    c = rng.normal(size=(32, 16)) * 4
    db = (c[rng.integers(0, 32, 2500)] + rng.normal(size=(2500, 16))).astype(np.float32)
    q = db[:40] + rng.normal(size=(40, 16)).astype(np.float32) * 0.05
    idx = build_two_level(db, TwoLevelConfig(n_clusters=50, top="brute",
                          bottom="brute", kmeans_iters=5))
    Kp = -(-50 // 8) * 8
    d, i = ShardedSearchBackend(mesh, idx, k=10, nprobe_local=Kp // 8)(q)
    ds, js, _ = idx.search(q, 10, nprobe=50)
    ok_d = np.allclose(np.sort(d), np.sort(ds), rtol=1e-4, atol=1e-4)
    ok_i = all(set(i[b].tolist()) == set(js[b].tolist()) for b in range(40))
    print("IDENT", bool(ok_d and ok_i))
    """)
    assert "IDENT True" in out


@slow
@pytest.mark.parametrize("delta_updates", [True, False],
                         ids=["slab", "packed"])
def test_sharded_forest_full_probe_identical_to_single_device(delta_updates):
    """Every shard descends the same per-bucket trees the single-device
    forest holds; with every bucket probed on both sides the candidate
    sets coincide, so the merged (id, distance) sets must be identical —
    in both forest layouts, with bucket-grid padding (K % shards != 0)."""
    out = _run(f"    delta_updates = {delta_updates}\n" + """
    from repro.distributed import ShardedSearchBackend
    from repro.core.two_level import TwoLevelConfig, build_two_level
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(4)
    c = rng.normal(size=(32, 16)) * 4
    db = (c[rng.integers(0, 32, 2700)] + rng.normal(size=(2700, 16))).astype(np.float32)
    q = db[:40] + rng.normal(size=(40, 16)).astype(np.float32) * 0.05
    idx = build_two_level(db, TwoLevelConfig(n_clusters=50, top="brute",
                          bottom="tree", kmeans_iters=5, tree_leaf=8))
    Kp = -(-50 // 8) * 8
    d, i = ShardedSearchBackend(mesh, idx, k=10, nprobe_local=Kp // 8,
                                beam_width=8,
                                delta_updates=delta_updates)(q)
    ds, js, _ = idx.search(q, 10, nprobe=50, beam_width=8)
    ok_d = np.allclose(np.sort(d), np.sort(ds), rtol=1e-4, atol=1e-4)
    ok_i = all(set(i[b].tolist()) == set(js[b].tolist()) for b in range(40))
    print("IDENT", bool(ok_d and ok_i))
    """)
    assert "IDENT True" in out


@slow
def test_serving_engine_sharded_survives_mutation_without_rejit():
    """Acceptance: ServingEngine.sharded keeps answering through a 30%
    interleaved add/delete + rebalance — deleted ids never served, the
    jitted search kernel's compile cache is untouched (no re-jit)."""
    out = _run("""
    from repro.serve.engine import ServingEngine
    from repro.core.two_level import TwoLevelConfig, build_two_level
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(5)
    c = rng.normal(size=(32, 16)) * 4
    def mk(n):
        return (c[rng.integers(0, 32, n)] + rng.normal(size=(n, 16))).astype(np.float32)
    db = mk(3000)
    idx = build_two_level(db, TwoLevelConfig(n_clusters=64, top="brute",
                          bottom="tree", kmeans_iters=4, tree_leaf=8))
    eng = ServingEngine.sharded(mesh, idx, kind="forest", k=10,
                                nprobe_local=4, beam_width=8, headroom=1.5,
                                max_batch=16, max_wait_ms=2.0)
    q = mk(48)
    futs = [eng.submit(q[j]) for j in range(48)]
    _ = [f.get(timeout=120) for f in futs]
    cache0 = eng.search_fn.jit_cache_size()
    deleted = []
    for r in range(3):
        live = np.nonzero(idx.alive)[0]
        dele = rng.choice(live, 300, replace=False)
        idx.delete_entities(dele); deleted.append(dele)
        idx.add_entities(mk(300))
    idx.rebalance()
    eng.apply_updates(idx)
    deleted = np.concatenate(deleted)
    futs = [eng.submit(q[j]) for j in range(48)]
    ids = np.stack([f.get(timeout=120)[1] for f in futs])
    cache1 = eng.search_fn.jit_cache_size()
    eng.close()
    print("CACHE", cache0, cache1, "CLEAN", bool(not np.isin(ids, deleted).any()))
    """)
    parts = out.split()
    c0 = int(parts[parts.index("CACHE") + 1])
    c1 = int(parts[parts.index("CACHE") + 2])
    assert "CLEAN True" in out
    assert c1 == c0, f"search kernel re-jitted: {c0} -> {c1}"


@slow
def test_sharded_delta_apply_identical_to_full_8dev():
    """Real 8-device mesh: a delta apply must leave the backend bitwise
    identical to a full re-place of the same mutated index, ship a small
    fraction of the full bytes for a localized mutation, and never touch
    the search kernel's compile cache."""
    out = _run("""
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed.backend import ShardedSearchBackend
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(6)
    c = rng.normal(size=(32, 16)) * 4
    def mk(n):
        return (c[rng.integers(0, 32, n)] + rng.normal(size=(n, 16))).astype(np.float32)
    db = mk(3000)
    idx = build_two_level(db, TwoLevelConfig(n_clusters=64, top="brute",
                          bottom="tree", kmeans_iters=4, tree_leaf=8))
    kw = dict(kind="forest", k=10, nprobe_local=4, beam_width=8, headroom=1.5)
    beA = ShardedSearchBackend(mesh, idx, **kw)
    beB = ShardedSearchBackend(mesh, idx, **kw)
    q = mk(32)
    dA0, _ = beA(q)
    cache0 = beA.jit_cache_size()
    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:10].copy()
    idx.delete_entities(dele)
    idx.add_entities(mk(12))
    man = idx.pop_delta()
    st = beA.apply_updates(idx, delta=man)
    beB.apply_updates(idx)
    same = all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(beA._args, beB._args))
    dA, iA = beA(q)
    dB, iB = beB(q)
    print("MODE", st["mode"], "FRAC", round(st["bytes"] / st["full_bytes"], 3),
          "SAME", bool(same and np.array_equal(dA, dB)
                       and np.array_equal(iA, iB)),
          "CACHE", cache0, beA.jit_cache_size(),
          "CLEAN", bool(not np.isin(iA, dele).any()))
    """)
    parts = out.split()
    assert "MODE delta" in out
    assert float(parts[parts.index("FRAC") + 1]) < 0.5
    assert "SAME True" in out and "CLEAN True" in out
    c0 = int(parts[parts.index("CACHE") + 1])
    c1 = int(parts[parts.index("CACHE") + 2])
    assert c1 == c0, f"search kernel re-jitted: {c0} -> {c1}"


@slow
def test_serving_engine_sharded_backend():
    """ServingEngine.sharded: exact sharded scan behind the micro-batcher
    returns the single-device answers."""
    out = _run("""
    from repro.serve.engine import ServingEngine
    from repro.core.brute import brute_search
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(2)
    db = rng.normal(size=(2000, 16)).astype(np.float32)
    eng = ServingEngine.sharded(mesh, db, k=5, max_batch=16, max_wait_ms=2.0)
    q = rng.normal(size=(40, 16)).astype(np.float32)
    futs = [eng.submit(q[j]) for j in range(40)]
    ids = np.stack([f.get(timeout=60)[1] for f in futs])
    eng.close()
    _, it = brute_search(q, db, 5)
    print("MATCH", float((ids == it).mean()))
    """)
    assert "MATCH 1.0" in out


@slow
def test_compressed_psum_approximates_mean():
    out = _run("""
    from jax import shard_map
    from repro.train.compression import compressed_psum
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 64)).astype(np.float32)
    fn = shard_map(lambda s: compressed_psum(s[0], "data"),
                   mesh=mesh, in_specs=P("data", None),
                   out_specs=P(None), check_vma=False)
    got = np.asarray(fn(x))
    want = x.mean(0)
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    print("ERR", err)
    """)
    assert float(out.split("ERR")[1]) < 0.05


@slow
def test_elastic_reshard_restore_1_to_8_devices():
    out = _run("""
    import tempfile
    from repro.train import checkpoint as C
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 1, tree)                      # saved "single-host"
        mesh = make_mesh((2, 4), ("data", "model"))
        shard = {"w": NamedSharding(mesh, P("data", "model")),
                 "b": NamedSharding(mesh, P("model"))}
        out = C.restore(d, 1, tree, shardings=shard)
        ok1 = (np.asarray(out["w"]) == np.asarray(tree["w"])).all()
        ok2 = len(out["w"].sharding.device_set) == 8
        print("OK", bool(ok1 and ok2))
    """)
    assert "OK True" in out


@slow
def test_lm_train_step_sharded_equals_local():
    """One train step on a 2x4 mesh == the same step on one device."""
    out = _run("""
    from repro.configs.base import LMConfig
    from repro.models import transformer as T
    from repro.distributed.sharding import ShardPlan
    from repro.train import optim
    from repro.train.loop import init_state, make_train_step
    from repro.data.lm import LMStream

    cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                   qk_norm=True, remat=False)
    key = jax.random.PRNGKey(0)
    stream = LMStream(cfg.vocab, 16, 8, seed=0)
    batch = stream.batch_at(0)
    opt = optim.adamw(optim.constant_lr(1e-3))

    # local
    s0 = init_state(T.init(cfg, key), opt)
    local_step = jax.jit(make_train_step(
        lambda p, b: T.loss_fn(p, b, cfg), opt))
    s1, aux1 = local_step(s0, batch)

    # sharded
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = ShardPlan(dp=("data",), fsdp=("data",), tp=("model",),
                     ep=("data", "model"), mesh=mesh)
    s0b = init_state(T.init(cfg, key), opt)
    sh_step = jax.jit(make_train_step(
        lambda p, b: T.loss_fn(p, b, cfg, plan), opt))
    with mesh:
        s2, aux2 = sh_step(s0b, batch)
    da = abs(float(aux1["loss"]) - float(aux2["loss"]))
    pa = np.asarray(jax.tree.leaves(s1.params)[0])
    pb = np.asarray(jax.tree.leaves(s2.params)[0])
    print("LOSSDIFF", da, "PARAMDIFF", float(np.abs(pa - pb).max()))
    """)
    parts = out.split()
    loss_diff = float(parts[parts.index("LOSSDIFF") + 1])
    param_diff = float(parts[parts.index("PARAMDIFF") + 1])
    assert loss_diff < 1e-3
    assert param_diff < 1e-3
