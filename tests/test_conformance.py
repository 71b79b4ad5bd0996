"""Cross-algorithm conformance suite for the two-level index.

Every ``top x bottom`` combination of :class:`TwoLevelConfig` must satisfy
the same contract, checked per combo on seeded random cases (``proptest``):

  (a) returned ids are unique per query (the rerank dedupe holds);
  (b) recall@k vs ``l2_topk_exact`` is monotone non-decreasing in
      ``nprobe`` (exact for the brute bottom — more probes mean a
      candidate *superset*; a small slack for LSH, whose fixed-size
      Hamming shortlist is not a superset under more probes);
  (c) results are invariant to corpus row permutation: exact (id-set)
      invariance at full probe for the brute bottom, recall-parity for
      the approximate bottoms (their build order legitimately shapes the
      tree/code structure).

Shapes are pinned (same n/d/K/cap across cases) so every case after the
first hits the jit cache.
"""
import numpy as np
import pytest

from proptest import run_cases
from repro.core.brute import brute_search
from repro.core.metrics import recall_at_k
from repro.launch.mesh import make_mesh
from repro.core.two_level import (
    BOTTOM_ALGOS,
    TOP_ALGOS,
    TwoLevelConfig,
    build_two_level,
)

N, D, K, CAP, NQ, TOPK = 600, 8, 16, 96, 16, 10
COMBOS = [(t, b) for t in TOP_ALGOS for b in BOTTOM_ALGOS]


def _corpus(rng, n):
    c = rng.normal(size=(8, D)) * 4
    return (c[rng.integers(0, 8, n)]
            + rng.normal(size=(n, D))).astype(np.float32)


def _build(db, top, bottom, p):
    cfg = TwoLevelConfig(
        n_clusters=K, top=top, bottom=bottom, kmeans_iters=3,
        kmeans_minibatch=None, bucket_cap=CAP, tree_leaf=4,
        lsh_bits=32, pq_m=4,
    )
    return build_two_level(db, cfg, p=p)


def _search_ids(idx, q, nprobe, k=TOPK):
    # LSH keeps a fixed-size Hamming shortlist, which is NOT a candidate
    # superset as nprobe grows; scale the rerank budget with the probe
    # count so the monotonicity contract tests the algorithm, not an
    # artificially starved shortlist.
    d, i, _ = idx.search(q, k, nprobe=nprobe, beam_width=8,
                         lsh_candidates=64 * nprobe)
    return np.asarray(d), np.asarray(i)


@pytest.mark.parametrize("top,bottom", COMBOS)
def test_conformance_sweep(top, bottom):
    run_cases(
        _conformance_property, n_cases=2,
        base_seed=TOP_ALGOS.index(top) * 10 + BOTTOM_ALGOS.index(bottom),
        top=top, bottom=bottom)


def _conformance_property(case, top, bottom):
    rng = case.rng
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = _build(db, top, bottom, p)
    q = _corpus(rng, NQ)
    _, i_true = brute_search(q, db, TOPK)

    # (a) unique ids per query, at partial and full probe
    for nprobe in (4, K):
        _, ids = _search_ids(idx, q, nprobe)
        for b in range(NQ):
            real = ids[b][ids[b] >= 0]
            assert len(set(real.tolist())) == len(real), (
                f"{top}/{bottom} nprobe={nprobe}: duplicate ids {ids[b]}")

    # (b) recall monotone non-decreasing in nprobe
    recalls = []
    for nprobe in (1, 4, K):
        _, ids = _search_ids(idx, q, nprobe)
        recalls.append(recall_at_k(ids, i_true))
    slack = 0.05 if bottom == "lsh" else 1e-9
    assert all(b >= a - slack for a, b in zip(recalls, recalls[1:])), (
        f"{top}/{bottom}: recall not monotone in nprobe: {recalls}")

    # (c) corpus row permutation invariance
    perm = rng.permutation(N)
    p_perm = None if p is None else p[perm]
    idx_p = _build(db[perm], top, bottom, p_perm)
    d0, i0 = _search_ids(idx, q, K)
    dp, ip = _search_ids(idx_p, q, K)
    ip_mapped = np.where(ip >= 0, perm[np.maximum(ip, 0)], -1)
    if bottom == "brute":
        # full probe == exact scan -> identical answer sets
        np.testing.assert_allclose(dp, d0, rtol=1e-4, atol=1e-4)
        for b in range(NQ):
            assert set(ip_mapped[b].tolist()) == set(i0[b].tolist()), (
                f"{top}/{bottom}: permuted corpus changed the exact "
                f"result set")
    else:
        r0 = recall_at_k(i0, i_true)
        rp = recall_at_k(ip_mapped, i_true)
        assert abs(r0 - rp) < 0.25, (
            f"{top}/{bottom}: permutation moved recall "
            f"{r0:.3f} -> {rp:.3f}")


# ---------------------------------------------------------------------------
# adaptive paths: the same contract must hold after a reboost and through
# the serving cache (PR-4 acceptance: results after any reboost or cache
# invalidation never contain deleted or stale entries)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top", TOP_ALGOS)
def test_conformance_reboosted(top):
    """(a)/(b) from the main contract, re-checked on a mutated-then-
    reboosted qlbt index: unique ids, no deleted ids at partial and full
    probe, recall still monotone in nprobe."""
    rng = np.random.default_rng(100 + TOP_ALGOS.index(top))
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5))
    idx = _build(db, top, "qlbt", p)
    dele = rng.choice(N, 60, replace=False)
    idx.delete_entities(dele)
    idx.reboost(rng.dirichlet(np.full(N, 0.5)))
    q = _corpus(rng, NQ)
    live = np.setdiff1d(np.arange(N), dele)
    _, i_true = brute_search(q, db[live], TOPK)
    recalls = []
    for nprobe in (1, 4, K):
        _, ids = _search_ids(idx, q, nprobe)
        assert not np.isin(ids, dele).any(), (
            f"{top}/qlbt reboosted: deleted id returned")
        for b in range(NQ):
            real = ids[b][ids[b] >= 0]
            assert len(set(real.tolist())) == len(real), (
                f"{top}/qlbt reboosted: duplicate ids")
        recalls.append(recall_at_k(ids, live[i_true]))
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:])), (
        f"{top}/qlbt reboosted: recall not monotone: {recalls}")


# ---------------------------------------------------------------------------
# delta shipping: applying a popped DeltaManifest must be indistinguishable
# from a full re-place — bitwise, on every combo (PR-5 acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top,bottom", COMBOS)
def test_conformance_delta_parity(top, bottom):
    """``apply_updates(delta=...)`` and a full re-place of the same
    mutated index must produce *bitwise-identical* device state and
    search results, for every top x bottom combo, and the localized
    mutation must actually take the delta path (not silently fall back).
    """
    import jax

    from repro.distributed.backend import ShardedSearchBackend

    rng = np.random.default_rng(300 + TOP_ALGOS.index(top) * 10
                                + BOTTOM_ALGOS.index(bottom))
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = _build(db, top, bottom, p)
    mesh = make_mesh((1,), ("data",))
    kw = dict(k=TOPK, axes=("data",), nprobe_local=K, beam_width=8,
              headroom=1.5)
    be_delta = ShardedSearchBackend(mesh, idx, **kw)
    be_full = ShardedSearchBackend(mesh, idx, **kw)

    # localized mutation: empty a few slots of one bucket, add mass near
    # another centroid — the dirty set stays a handful of buckets
    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:5].copy()
    idx.delete_entities(dele)
    new = (idx.centroids[1][None, :]
           + 0.1 * rng.normal(size=(5, D))).astype(np.float32)
    idx.add_entities(new)

    man = idx.pop_delta()
    st = be_delta.apply_updates(idx, delta=man)
    assert st["mode"] == "delta", st
    assert st["bytes"] < st["full_bytes"]
    be_full.apply_updates(idx)                    # full re-place control

    # device state parity: every placed array identical bit for bit
    for a, b in zip(be_delta._args, be_full._args):
        assert a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))

    q = _corpus(rng, NQ)
    d1, i1 = be_delta(q)
    d2, i2 = be_full(q)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2), (
        f"{top}/{bottom}: delta apply diverged from full re-place")
    assert not np.isin(i1, dele).any(), (
        f"{top}/{bottom}: deleted id returned through the delta path")


# ---------------------------------------------------------------------------
# kernel body vs reference: the sharded scans dispatch through
# ``repro.kernels.ops``, which runs the Pallas kernel on the chip and the
# jnp reference elsewhere; the two must agree on the fresh index AND after
# a mutation shipped as a delta
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top,bottom", COMBOS)
def test_conformance_kernel_body_vs_reference(top, bottom, monkeypatch):
    """The same backend traced through the Pallas kernel body (interpret
    mode) and through the reference dispatch returns identical ids — and
    bitwise-identical distances for the IVF kinds; the forest rerank
    accumulates in another order, so its distances agree to 1e-5 — on
    the fresh index and after a localized mutation applied through the
    delta path on both backends."""
    from conftest import pallas_kernels
    from repro.distributed.backend import ShardedSearchBackend

    rng = np.random.default_rng(500 + TOP_ALGOS.index(top) * 10
                                + BOTTOM_ALGOS.index(bottom))
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = _build(db, top, bottom, p)
    mesh = make_mesh((1,), ("data",))
    kw = dict(k=TOPK, axes=("data",), nprobe_local=K, beam_width=8,
              headroom=1.5)
    be_ref = ShardedSearchBackend(mesh, idx, **kw)
    be_pal = ShardedSearchBackend(mesh, idx, **kw)
    q = _corpus(rng, NQ)

    def agree(tag):
        dr, ir = be_ref(q)
        with pallas_kernels(monkeypatch):
            dp, ip = be_pal(q)
        assert np.array_equal(ip, ir), (
            f"{top}/{bottom} [{tag}]: kernel body ids diverged from the "
            f"reference")
        if be_ref.kind == "forest":
            np.testing.assert_allclose(dp, dr, rtol=1e-5, atol=1e-5)
        else:
            assert np.array_equal(dp, dr), (
                f"{top}/{bottom} [{tag}]: kernel body distances diverged "
                f"from the reference")

    agree("fresh")

    # localized mutation -> delta apply on BOTH -> still equal
    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:5].copy()
    idx.delete_entities(dele)
    new = (idx.centroids[1][None, :]
           + 0.1 * rng.normal(size=(5, D))).astype(np.float32)
    idx.add_entities(new)
    man = idx.pop_delta()
    st_ref = be_ref.apply_updates(idx, delta=man)
    st_pal = be_pal.apply_updates(idx, delta=man)
    assert st_ref["mode"] == st_pal["mode"] == "delta", (st_ref, st_pal)
    agree("post-delta")
    _, i_r = be_ref(q)
    assert not np.isin(i_r, dele).any(), (
        f"{top}/{bottom}: deleted id returned through the delta path")


def test_conformance_int8_brute_recall():
    """The int8-footprint brute scan is approximate (quantization), not
    bitwise — but it must track the f32 scan closely: recall@k vs the
    f32 result near 1, and survive the delta path (tombstone flips, and
    appended rows quantized on the way in)."""
    import jax

    from repro.core.delta import DeltaManifest
    from repro.distributed.backend import ShardedSearchBackend

    rng = np.random.default_rng(600)
    db = _corpus(rng, N)
    mesh = make_mesh((1,), ("data",))
    kw = dict(k=TOPK, axes=("data",), headroom=1.5)
    be32 = ShardedSearchBackend(mesh, db, precision="f32", **kw)
    be8 = ShardedSearchBackend(mesh, db, precision="int8", **kw)
    q = _corpus(rng, NQ)
    _, i32 = be32(q)
    _, i8 = be8(q)
    assert recall_at_k(np.asarray(i8), np.asarray(i32)) > 0.9, (
        "int8 scan strayed too far from the f32 scan")

    # tombstone window, then an append window — both down the delta path
    dele = np.asarray([3, 17, 41])
    man = DeltaManifest(base_version=0, version=1, base_n=N, n=N,
                        tombstones=dele)
    assert be8.apply_updates(db, delta=man)["mode"] == "delta"
    be32.apply_updates(db, delta=man)
    grown = np.concatenate([db, _corpus(rng, 8)])
    man2 = DeltaManifest(base_version=1, version=2, base_n=N, n=N + 8)
    st = be8.apply_updates(grown, delta=man2)
    assert st["mode"] == "delta", st
    be32.apply_updates(grown, delta=man2)
    _, i32 = be32(q)
    _, i8 = be8(q)
    assert not np.isin(i8, dele).any(), "int8 delta path returned deleted id"
    assert recall_at_k(np.asarray(i8), np.asarray(i32)) > 0.9


# ---------------------------------------------------------------------------
# fleet conformance: a routed fleet is indistinguishable from one engine —
# bitwise on results, and bitwise on every cell's device state after a
# leader delta fan-out (PR-7 acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top,bottom", COMBOS)
def test_conformance_fleet_bitwise(top, bottom):
    """Routing must be a pure placement decision: every query answered
    through the ``CellRouter`` is bitwise-identical to a standalone
    control backend, and a leader fan-out (ONE popped manifest applied
    to every cell) leaves every cell's device state bitwise-identical
    to a single-cell delta apply — for every top x bottom combo."""
    from repro.distributed.backend import ShardedSearchBackend
    from repro.launch.mesh import make_cell_meshes
    from repro.serve.fleet import build_fleet

    rng = np.random.default_rng(400 + TOP_ALGOS.index(top) * 10
                                + BOTTOM_ALGOS.index(bottom))
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = _build(db, top, bottom, p)
    meshes = make_cell_meshes(2, share_devices=True)
    bkw = dict(nprobe_local=K, beam_width=8, headroom=1.5)
    control = ShardedSearchBackend(
        meshes[0], idx, k=TOPK, axes=tuple(meshes[0].axis_names), **bkw)
    router = build_fleet(meshes, idx, k=TOPK, backend_kw=bkw,
                         cell_kw=dict(max_wait_ms=0.5))
    try:
        q = _corpus(rng, 8)

        def routed_matches_control():
            for j in range(q.shape[0]):
                dr, ir = router.search(q[j], timeout=30.0)
                dc, ic = control(q[j:j + 1])
                assert np.array_equal(dr, dc[0]) and \
                    np.array_equal(ir, ic[0]), (
                        f"{top}/{bottom}: routed result diverged from "
                        f"the standalone engine")

        routed_matches_control()

        # localized mutation -> ONE pop -> leader fan-out vs single-cell
        b = int(np.argmax(idx.bucket_counts))
        dele = idx.bucket_ids[b][:5].copy()
        idx.delete_entities(dele)
        new = (idx.centroids[1][None, :]
               + 0.1 * rng.normal(size=(5, D))).astype(np.float32)
        idx.add_entities(new)
        man = idx.pop_delta()
        agg = router.apply_updates(idx, delta=man)
        assert agg["mode"] == "delta", agg
        assert set(agg["cells"]) == {c.name for c in router.cells}
        control.apply_updates(idx, delta=man)

        for cell in router.cells:
            for a, c in zip(cell.search_fn._args, control._args):
                assert a.shape == c.shape
                assert np.array_equal(np.asarray(a), np.asarray(c)), (
                    f"{top}/{bottom}: {cell.name} device state diverged "
                    f"from single-cell delta apply")

        routed_matches_control()
        ir = np.stack([router.search(q[j], timeout=30.0)[1]
                       for j in range(q.shape[0])])
        assert not np.isin(ir, dele).any(), (
            f"{top}/{bottom}: deleted id returned through the fleet")
    finally:
        router.close()


def test_conformance_cached_serving_never_stale():
    """The cached serving path must track mutations: a result cached
    before delete+reboost+apply_updates can never resurface."""
    from repro.adaptive import FrequencyAdmissionCache, HostIndexBackend
    from repro.serve.engine import ServingEngine

    rng = np.random.default_rng(200)
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5))
    idx = _build(db, "brute", "qlbt", p)
    backend = HostIndexBackend(idx, k=5, nprobe=K, beam_width=16)
    cache = FrequencyAdmissionCache(capacity=64)
    eng = ServingEngine(backend, cache=cache, max_wait_ms=0.5)
    try:
        target = int(rng.integers(0, N))
        q = db[target].copy()
        _, ids0 = eng.search(q, timeout=30.0)
        assert target in ids0
        _, ids1 = eng.search(q, timeout=30.0)          # served from cache
        assert eng.stats().cache_hits >= 1
        idx.delete_entities(np.asarray([target]))
        idx.reboost(rng.dirichlet(np.full(N, 0.5)))
        eng.apply_updates(idx)                          # invalidates cache
        _, ids2 = eng.search(q, timeout=30.0)
        assert target not in ids2, "cache served a deleted entity"
    finally:
        eng.close()
