"""Online index mutation: add/delete/rebalance lifecycle.

Parity contract (the tentpole's acceptance): after interleaved adds and
deletes, searching the mutated index must match searching an index built
from scratch on the same surviving corpus — *exactly* for the brute
bottom at full probe (both are exact scans over the survivors), and
recall-bounded for the approximate bottoms (qlbt forest / LSH), whose
structures legitimately differ between an incremental and a fresh build.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.brute import brute_search
from repro.core.index import build_index
from repro.core.metrics import recall_at_k
from repro.core.protocol import IndexSpec
from repro.core.two_level import TwoLevelConfig, build_two_level
from repro.launch.mesh import make_mesh

N, D, K = 1500, 12, 24


def _gen(seed):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=(12, D)) * 4

    def mk(n):
        return (c[rng.integers(0, 12, n)]
                + rng.normal(size=(n, D))).astype(np.float32)

    return rng, mk


def _cfg(bottom, **kw):
    kw.setdefault("tree_leaf", 8)
    return TwoLevelConfig(n_clusters=K, top="brute", bottom=bottom,
                          kmeans_iters=4, kmeans_minibatch=None, **kw)


def _mutate_30pct(idx, mk, seed, rounds=3, chunk=75):
    """Interleave ``rounds`` x (delete chunk, add chunk) ~= 30% of N."""
    rng = np.random.default_rng(seed)
    deleted = []
    for _ in range(rounds):
        live = np.nonzero(idx.alive)[0]
        dele = rng.choice(live, chunk, replace=False)
        idx.delete_entities(dele)
        deleted.append(dele)
        idx.add_entities(mk(chunk))
    return np.concatenate(deleted)


# ---------------------------------------------------------------------------
# basic visibility / invisibility invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bottom", ["brute", "tree", "qlbt", "lsh"])
def test_mutation_visibility_all_bottoms(bottom):
    """Adds are findable, deletes unreturnable, bucket invariants hold."""
    rng, mk = _gen(0)
    db = mk(N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = build_two_level(db, _cfg(bottom), p=p)
    deleted = _mutate_30pct(idx, mk, seed=1)

    # every live entity sits in exactly one bucket slot, no deleted slot
    flat = idx.bucket_ids[idx.bucket_ids >= 0]
    live = np.nonzero(idx.alive)[0]
    assert sorted(flat.tolist()) == live.tolist()
    assert np.array_equal(
        idx.bucket_counts,
        (idx.bucket_ids >= 0).sum(axis=1).astype(idx.bucket_counts.dtype))

    q = mk(64)
    _, ids, _ = idx.search(q, 10, nprobe=K, beam_width=16)
    assert not np.isin(ids, deleted).any(), "deleted id returned"

    # freshly added entities are findable (query = the vectors themselves)
    new = idx.db[live[live >= N]][:32]
    if new.shape[0]:
        _, ids, _ = idx.search(new, 1, nprobe=K, beam_width=16)
        assert (np.asarray(ids)[:, 0] >= N).mean() > 0.85


def test_deleted_forest_leaves_are_masked_without_rebuild():
    """A tree-bottom delete must be invisible even with refresh deferred:
    the leaf slots are blanked in place (bounded staleness, never wrong)."""
    rng, mk = _gen(2)
    db = mk(600)
    idx = build_two_level(db, _cfg("tree", tree_leaf=4))
    target = np.asarray([5, 17, 300])
    idx.delete_entities(target)
    le = np.asarray(idx.forest.arrays["leaf_entities"])
    assert not np.isin(le, target).any()
    q = idx.db[target] + 0.0          # query exactly the deleted vectors
    _, ids, _ = idx.search(q, 5, nprobe=K, beam_width=16)
    assert not np.isin(ids, target).any()


def test_slot_reuse_and_no_pad_growth():
    """Tombstoned slots are compacted and reused: delete m then add m must
    not grow the bucket pad width."""
    rng, mk = _gen(3)
    db = mk(800)
    idx = build_two_level(db, _cfg("brute"))
    cap0 = idx.bucket_ids.shape[1]
    dele = rng.choice(800, 120, replace=False)
    idx.delete_entities(dele)
    idx.add_entities(mk(120))
    assert idx.bucket_ids.shape[1] == cap0
    assert idx.n_live == 800


def test_add_validates_partition_features_both_ways():
    rng, mk = _gen(4)
    db = mk(400)
    feats = db[:, :3].copy()
    idx = build_two_level(db, _cfg("brute"), partition_features=feats)
    with pytest.raises(ValueError, match="partition_features"):
        idx.add_entities(mk(8))                      # missing
    with pytest.raises(ValueError, match="rows for"):
        idx.add_entities(mk(8), partition_features=feats[:3])  # wrong len
    new = mk(8)
    ids = idx.add_entities(new, partition_features=new[:, :3])
    assert ids.size == 8 and idx.part_feats.shape[0] == 408
    # ...and the reverse direction: features on a plain-embedding index
    # would be silently ignored, so it must refuse
    idx2 = build_two_level(db, _cfg("brute"))
    with pytest.raises(ValueError, match="ignored"):
        idx2.add_entities(new, partition_features=new[:, :3])


def test_deferred_refresh_bounded_staleness():
    """``refresh=False`` defers the dirty-bucket rebuild: new entities are
    invisible to the forest descent (stale, not wrong) until
    ``refresh_forest()`` — after which they are findable."""
    rng, mk = _gen(5)
    db = mk(600)
    idx = build_two_level(db, _cfg("tree", tree_leaf=4))
    new = mk(40)
    ids = idx.add_entities(new, refresh=False)
    assert idx.dirty.any()
    _, got, _ = idx.search(new, 1, nprobe=K, beam_width=16)
    assert not np.isin(got, ids).any()           # stale: not yet descended
    rebuilt = idx.refresh_forest()
    assert rebuilt > 0 and not idx.dirty.any()
    _, got, _ = idx.search(new, 1, nprobe=K, beam_width=16)
    assert (np.asarray(got)[:, 0] >= 600).mean() > 0.85


# ---------------------------------------------------------------------------
# mutation parity vs from-scratch rebuild
# ---------------------------------------------------------------------------


def test_interleaved_mutation_exact_parity_brute():
    """Brute bottom at full probe is an exact scan over the survivors, so
    the mutated index, a from-scratch rebuild, and the oracle must agree
    (id sets per query; distances to float tolerance)."""
    rng, mk = _gen(6)
    db = mk(N)
    idx = build_two_level(db, _cfg("brute"))
    _mutate_30pct(idx, mk, seed=7)
    live = np.nonzero(idx.alive)[0]
    surv = idx.db[live]
    idx2 = build_two_level(surv, _cfg("brute"))
    q = mk(64)
    d0, i0 = brute_search(q, surv, 10)
    d1, i1, _ = idx.search(q, 10, nprobe=K)
    d2, i2, _ = idx2.search(q, 10, nprobe=K)
    np.testing.assert_allclose(np.asarray(d1), d0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(d2), d0, rtol=1e-4, atol=1e-4)
    # map mutated-index global ids -> surviving-corpus row ids
    inv = np.full(idx.n, -1, np.int64)
    inv[live] = np.arange(live.size)
    for b in range(q.shape[0]):
        assert set(inv[i1[b]].tolist()) == set(i0[b].tolist())
        assert set(np.asarray(i2[b]).tolist()) == set(i0[b].tolist())


@pytest.mark.parametrize("bottom", ["qlbt", "lsh"])
def test_interleaved_mutation_recall_bounded(bottom):
    """Approximate bottoms: the mutated index's recall@10 must stay within
    0.1 of a from-scratch rebuild on the surviving corpus."""
    rng, mk = _gen(8)
    db = mk(N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    idx = build_two_level(db, _cfg(bottom), p=p)
    _mutate_30pct(idx, mk, seed=9)
    live = np.nonzero(idx.alive)[0]
    surv = idx.db[live]
    p2 = None if idx.p is None else idx.p[live]
    idx2 = build_two_level(surv, _cfg(bottom), p=p2)
    q = mk(64)
    _, it = brute_search(q, surv, 10)
    _, i1, _ = idx.search(q, 10, nprobe=8, beam_width=8)
    _, i2, _ = idx2.search(q, 10, nprobe=8, beam_width=8)
    r_mut = recall_at_k(np.asarray(i1), live[it])
    r_new = recall_at_k(np.asarray(i2), it)
    assert r_mut > r_new - 0.1, f"{bottom}: {r_mut:.3f} vs {r_new:.3f}"


def test_rebalance_acceptance_30pct_within_one_point():
    """Acceptance: 30% interleaved adds/deletes + one rebalance() -> the
    mutated qlbt index's recall@10 is within 1 point of a from-scratch
    rebuild on the same corpus (beam wide enough that the per-bucket
    descent is near-exhaustive — measuring the *index*, not the beam)."""
    rng, mk = _gen(10)
    db = mk(N)
    p = rng.dirichlet(np.full(N, 0.5))
    idx = build_two_level(db, _cfg("qlbt"), p=p)
    _mutate_30pct(idx, mk, seed=11)
    stats = idx.rebalance()
    assert stats["n_rebuilt_buckets"] >= 0 and not idx.dirty.any()
    live = np.nonzero(idx.alive)[0]
    surv = idx.db[live]
    idx2 = build_two_level(surv, _cfg("qlbt"), p=idx.p[live])
    q = mk(64)
    _, it = brute_search(q, surv, 10)
    _, i1, _ = idx.search(q, 10, nprobe=12, beam_width=32)
    _, i2, _ = idx2.search(q, 10, nprobe=12, beam_width=32)
    r_mut = recall_at_k(np.asarray(i1), live[it])
    r_new = recall_at_k(np.asarray(i2), it)
    assert r_mut >= r_new - 0.01, f"{r_mut:.4f} vs rebuilt {r_new:.4f}"


def test_rebalance_recenters_drifted_buckets():
    """Skewed growth (every add lands in one region) must trip the drift
    detector: rebalance recenters and re-routes, leaving every entity in
    exactly one slot and centroids closer to their members."""
    rng, mk = _gen(12)
    db = mk(1000)
    idx = build_two_level(db, _cfg("brute"))
    # pour new mass into one corner of the space
    shift = np.zeros(D, np.float32)
    shift[0] = 6.0
    new = mk(300) * 0.25 + shift
    idx.add_entities(new.astype(np.float32))
    stats = idx.rebalance(drift_threshold=0.2)
    assert stats["n_drifted"] >= 1
    assert stats["n_moved"] >= 0
    flat = idx.bucket_ids[idx.bucket_ids >= 0]
    assert sorted(flat.tolist()) == np.nonzero(idx.alive)[0].tolist()
    # recall is intact after the re-route
    q = mk(32)
    live = np.nonzero(idx.alive)[0]
    _, it = brute_search(q, idx.db[live], 10)
    _, ids, _ = idx.search(q, 10, nprobe=K)
    assert recall_at_k(np.asarray(ids), live[it]) > 0.95


# ---------------------------------------------------------------------------
# SearchIndex-level lifecycle (single-tree protocol path)
# ---------------------------------------------------------------------------


def test_search_index_single_tree_lifecycle():
    rng, mk = _gen(13)
    db = mk(500)
    p = rng.dirichlet(np.full(500, 0.5))
    si = build_index(IndexSpec(kind="qlbt"), db, p=p)
    ids = si.add_entities(mk(50))
    assert ids.tolist() == list(range(500, 550))
    si.delete_entities(np.arange(10))
    q = si.db[:10]
    _, got, _ = si.search(q, 5, beam_width=16)
    assert not np.isin(got, np.arange(10)).any()
    stats = si.rebalance()
    assert stats["n_rebuilt_buckets"] == 1
    _, got, _ = si.search(q, 5, beam_width=16)
    assert not np.isin(got, np.arange(10)).any()
    # surviving entities still findable after the rebuild
    probe = si.db[200:232]
    _, got, _ = si.search(probe, 1, beam_width=16)
    assert (np.asarray(got)[:, 0] == np.arange(200, 232)).mean() > 0.9


def test_engine_apply_updates_reaches_hedge_replica():
    """A hedge replica must be updated with the primary: a stale replica
    would serve deleted entities on every hedged request.  A hedge_fn
    without apply_updates is an error, not a silent staleness hole."""
    from repro.serve.engine import ServingEngine

    class _Backend:
        def __init__(self):
            self.seen = []

        def __call__(self, qs):
            b = qs.shape[0]
            return np.zeros((b, 1), np.float32), np.zeros((b, 1), np.int32)

        def apply_updates(self, target, **kw):
            self.seen.append(target)

    primary, replica = _Backend(), _Backend()
    eng = ServingEngine(primary, hedge_fn=replica, hedge_ms=1000.0)
    try:
        eng.apply_updates("snapshot-1")
        assert primary.seen == ["snapshot-1"]
        assert replica.seen == ["snapshot-1"]
        eng.hedge_fn = lambda qs: None          # replica w/o apply_updates
        with pytest.raises(TypeError, match="hedge_fn"):
            eng.apply_updates("snapshot-2")
        assert primary.seen == ["snapshot-1"]   # nothing half-applied
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# delta shipping (PR-5): manifest lifecycle, fallback boundaries, counters
# ---------------------------------------------------------------------------


def test_delta_manifest_accumulates_and_pops():
    """Mutations accumulate into one manifest; pop resets the log and
    chains versions; an untouched index pops an empty manifest."""
    rng, mk = _gen(20)
    db = mk(600)
    idx = build_two_level(db, _cfg("tree", tree_leaf=4))
    man0 = idx.pop_delta()
    assert man0.empty and man0.base_version == man0.version

    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:3].copy()
    idx.delete_entities(dele)
    ids = idx.add_entities(mk(4))
    man = idx.pop_delta()
    assert not man.empty and not man.full
    assert man.base_version == man0.version and man.version > man.base_version
    assert man.base_n == 600 and man.n == 604
    assert set(dele.tolist()) == set(man.tombstones.tolist())
    assert b in man.dirty_buckets.tolist()
    # every receiving bucket of the adds is named dirty
    for e in ids:
        assert int(idx.entity_bucket[e]) in man.dirty_buckets.tolist()
    # the pop cleared the log: next manifest is empty and chains on
    man2 = idx.pop_delta()
    assert man2.empty and man2.base_version == man.version

    # SearchIndex single-tree path: deletes are a delta, adds are full
    si = build_index(IndexSpec(kind="tree"), mk(300))
    si.delete_entities(np.arange(5))
    m = si.pop_delta()
    assert not m.full and m.tombstones.size == 5
    si.add_entities(mk(10))
    assert si.pop_delta().full        # whole-tree rebuild -> no delta


def _mesh1():
    return make_mesh((1,), ("data",))


def test_delta_threshold_boundary_falls_back_to_full():
    """The payload-vs-full size cutoff: the same manifest ships as a
    delta under a permissive threshold and falls back to a full re-place
    (reason="threshold") under a tight one — with identical results
    either way.  The localized mutation itself must cost <= 25% of a
    full re-place (the fig7 acceptance bound at <=10% mutation)."""
    from repro.distributed.backend import ShardedSearchBackend

    rng, mk = _gen(21)
    db = mk(N)
    idx = build_two_level(db, _cfg("tree"))
    mesh = _mesh1()
    kw = dict(k=10, axes=("data",), nprobe_local=K, beam_width=8,
              headroom=1.5)
    be = ShardedSearchBackend(mesh, idx, **kw)

    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:6].copy()
    idx.delete_entities(dele)
    man = idx.pop_delta()
    be.delta_max_fraction = 0.0                 # tighter than any payload
    st = be.apply_updates(idx, delta=man)
    assert st["mode"] == "full" and st["reason"] == "threshold"

    dele2 = idx.bucket_ids[b][:4].copy()
    idx.delete_entities(dele2)
    man2 = idx.pop_delta()
    be.delta_max_fraction = 1.0
    st2 = be.apply_updates(idx, delta=man2)
    assert st2["mode"] == "delta"
    assert st2["bytes"] <= 0.25 * st2["full_bytes"], (
        f"localized delta shipped {st2['bytes']} of "
        f"{st2['full_bytes']} bytes")
    q = mk(32)
    _, i1 = be(q)
    assert not np.isin(i1, np.concatenate([dele, dele2])).any()


def test_delta_version_mismatch_falls_back_to_full():
    """A manifest whose base version is AHEAD of what the backend last
    placed under-covers the backend's staleness (a pop went missing) —
    it must fall back to a full re-place, never apply partially."""
    from repro.distributed.backend import ShardedSearchBackend

    rng, mk = _gen(22)
    db = mk(N)
    idx = build_two_level(db, _cfg("tree"))
    mesh = _mesh1()
    be = ShardedSearchBackend(mesh, idx, k=10, axes=("data",),
                              nprobe_local=K, beam_width=8, headroom=1.5)
    b = int(np.argmax(idx.bucket_counts))
    d1 = idx.bucket_ids[b][:3].copy()
    idx.delete_entities(d1)
    idx.pop_delta()                       # popped but never applied
    d2 = idx.bucket_ids[b][:3].copy()
    idx.delete_entities(d2)
    man = idx.pop_delta()                 # base is ahead of the backend
    st = be.apply_updates(idx, delta=man)
    assert st["mode"] == "full" and st["reason"] == "version"
    q = mk(32)
    _, ids = be(q)
    assert not np.isin(ids, np.concatenate([d1, d2])).any()


def test_delta_full_manifest_and_missing_manifest_fall_back():
    """A ``full`` manifest (single-tree rebuild semantics) and a plain
    ``apply_updates`` without a manifest both take the bulk path."""
    from repro.distributed.backend import ShardedSearchBackend

    rng, mk = _gen(23)
    idx = build_two_level(mk(N), _cfg("brute"))
    mesh = _mesh1()
    be = ShardedSearchBackend(mesh, idx, k=10, axes=("data",),
                              nprobe_local=K, headroom=1.3)
    idx.add_entities(mk(8))
    st = be.apply_updates(idx)
    assert st["mode"] == "full" and st["reason"] == "no-manifest"
    idx.delete_entities(np.asarray([0]))
    man = idx.pop_delta()
    man = dataclasses.replace(man, full=True)
    st2 = be.apply_updates(idx, delta=man)
    assert st2["mode"] == "full" and st2["reason"] == "manifest-full"


def test_engine_delta_counters_and_cache_invalidation():
    """ServingEngine.apply_updates pops the manifest itself, ships the
    delta, surfaces republished_bytes / delta_fraction in EngineStats,
    and still invalidates the result cache (no stale hit can survive a
    delta republish any more than a full one)."""
    from repro.adaptive import FrequencyAdmissionCache
    from repro.distributed.backend import ShardedSearchBackend
    from repro.serve.engine import ServingEngine

    rng, mk = _gen(24)
    idx = build_two_level(mk(N), _cfg("tree"))
    mesh = _mesh1()
    be = ShardedSearchBackend(mesh, idx, k=5, axes=("data",),
                              nprobe_local=K, beam_width=16, headroom=1.5)
    cache = FrequencyAdmissionCache(capacity=64)
    eng = ServingEngine(be, cache=cache, max_wait_ms=0.5)
    try:
        b = int(np.argmax(idx.bucket_counts))
        target = int(idx.bucket_ids[b][0])
        q = idx.db[target].copy()
        _, ids0 = eng.search(q, timeout=30.0)
        assert target in ids0
        _, _ = eng.search(q, timeout=30.0)
        assert eng.stats().cache_hits >= 1
        idx.delete_entities(np.asarray([target]))
        st = eng.apply_updates(idx)       # pops + ships the delta
        assert st["mode"] == "delta"
        stats = eng.stats()
        assert stats.republished_bytes == st["bytes"] > 0
        assert 0.0 < stats.delta_fraction <= 0.25
        _, ids2 = eng.search(q, timeout=30.0)
        assert target not in ids2, "stale cached result after delta ship"
    finally:
        eng.close()


def test_reboost_refresh_of_stale_dirty_bucket_reenters_delta_log():
    """Regression: a bucket dirtied before a pop (deferred refresh) and
    rebuilt by a later reboost() must re-enter the CURRENT delta log —
    omitting it would delta-ship a stale slab and silently diverge from
    a full re-place."""
    rng, mk = _gen(26)
    db = mk(600)
    p = rng.dirichlet(np.full(600, 0.5))
    idx = build_two_level(db, _cfg("qlbt", tree_leaf=4), p=p)
    ids = idx.add_entities(mk(8), refresh=False)   # dirty, tree stale
    idx.pop_delta()                                # log reset, dirty stays
    b = {int(idx.entity_bucket[e]) for e in ids}
    assert idx.dirty.any()
    idx.reboost(rng.dirichlet(np.full(idx.n, 0.5)))  # rebuilds dirty trees
    man = idx.pop_delta()
    assert b <= set(man.dirty_buckets.tolist()), (
        "reboost-refreshed bucket missing from the delta manifest")


def test_brute_delta_applies_manifest_tombstones_without_alive():
    """The brute delta path must flip liveness for the manifest's
    tombstones even when the caller forgets the ``alive`` kwarg — a
    delta republish may never resurrect a tombstoned row."""
    from repro.core.delta import DeltaManifest
    from repro.distributed.backend import ShardedSearchBackend

    rng, mk = _gen(27)
    db = mk(400)
    mesh = _mesh1()
    be = ShardedSearchBackend(mesh, db, k=5, axes=("data",), headroom=1.5)
    man = DeltaManifest(base_version=0, version=1, base_n=400, n=400,
                        tombstones=np.asarray([7, 11]))
    st = be.apply_updates(db, delta=man)           # no alive kwarg
    assert st["mode"] == "delta"
    q = db[[7, 11]]
    _, ids = be(q)
    assert not np.isin(ids, [7, 11]).any(), "tombstoned row resurrected"
    # a LATER append-only window must not forget the earlier flips
    # (liveness is cumulative on the backend, not rebuilt per manifest)
    grown = np.concatenate([db, mk(20)])
    man2 = DeltaManifest(base_version=1, version=2, base_n=400, n=420)
    st2 = be.apply_updates(grown, delta=man2)
    assert st2["mode"] == "delta"
    _, ids = be(q)
    assert not np.isin(ids, [7, 11]).any(), (
        "earlier window's tombstones resurrected by a later delta")
    # and a manifest that skips a window in the chain falls back to full
    man4 = DeltaManifest(base_version=3, version=4, base_n=420, n=420,
                         tombstones=np.asarray([20]))
    st3 = be.apply_updates(grown, delta=man4)
    assert st3["mode"] == "full" and st3["reason"] == "version"


def test_scheduler_event_records_republish_stats():
    """A drift-triggered maintenance pass reports what its republish
    shipped (the host backend republishes by reference: zero bytes)."""
    from repro.adaptive import HostIndexBackend, MaintenanceScheduler
    from repro.serve.engine import ServingEngine

    rng, mk = _gen(25)
    db = mk(600)
    p = rng.dirichlet(np.full(600, 0.5))
    idx = build_two_level(db, _cfg("qlbt"), p=p)

    class _Est:                        # minimal estimator stub
        n_total = 1e6

        def drift(self):
            return {"tv": 1.0, "kl": 1.0, "n_observed": 1e6}

        def likelihood(self):
            return rng.dirichlet(np.full(600, 0.5))

        def set_reference(self, p):
            pass

    backend = HostIndexBackend(idx, k=5, nprobe=K)
    eng = ServingEngine(backend, max_wait_ms=0.5)
    sched = MaintenanceScheduler(_Est(), idx, engine=eng, interval_s=None,
                                 drift_threshold=0.5, min_observations=1)
    try:
        ev = sched.check_now()
        assert ev is not None
        assert ev["republish"]["mode"] == "swap"
        assert ev["republish"]["bytes"] == 0
        assert backend.last_delta is not None     # manifest reached it
    finally:
        sched.close()
        eng.close()


def test_search_index_single_tree_add_does_not_resurrect_deleted():
    """Regression: the single-tree add path rebuilds the whole tree; it
    must rebuild over the *survivors*, not the full db — a rebuild over
    every row silently resurrects tombstoned entities."""
    rng, mk = _gen(14)
    db = mk(400)
    si = build_index(IndexSpec(kind="tree"), db)
    dead = np.arange(7)
    si.delete_entities(dead)
    si.add_entities(mk(30))                 # delete THEN add
    q = db[dead]                            # query the deleted vectors
    _, got, _ = si.search(q, 5, beam_width=16)
    assert not np.isin(got, dead).any(), "deleted ids resurrected by add"
    si.rebalance()
    _, got, _ = si.search(q, 5, beam_width=16)
    assert not np.isin(got, dead).any(), "deleted ids resurrected by rebalance"
