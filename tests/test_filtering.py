"""Filtered + hybrid search conformance through the sharded scan.

The filter surface (``repro.core.metadata``) compiles predicates to row
masks that are *data, not shapes* — so the contract is strong:

  (a) a backend traced through the Pallas kernel body agrees with the
      same backend through the reference dispatch under every filter,
      at selectivities {0, 0.05, 0.5, 1.0}, on the fresh index AND after
      a localized mutation shipped down the delta path, for every top x
      bottom combo: identical ids, bitwise distances for the IVF kinds
      and distances within 1e-5 for the forest rerank;
  (b) no returned id ever violates the predicate (or a tombstone);
  (c) selectivity 0 yields the full ``(inf, -1)`` sentinel surface with
      no NaNs; a selectivity-1.0 predicate is bitwise-equal to the
      unfiltered call;
  (d) the brute kind is additionally *exact*: through either kernel,
      the ids of a pure numpy masked-scan oracle, fresh and post-delta;
  (e) lexical (BM25 slab) and hybrid modes match their numpy oracles
      and compose with filters, without minting jit signatures beyond
      the three per-mode callables;
  (f) the admission cache key isolates filter/mode/alpha: a filtered
      result can never satisfy an unfiltered request (or vice versa),
      and apply_updates still invalidates every variant.
"""
import jax
import numpy as np
import pytest

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

from conftest import pallas_kernels

N, D, K, CAP, NQ, TOPK = 600, 8, 16, 96, 16, 10
COMBOS = [(t, b) for t in TOP_ALGOS for b in BOTTOM_ALGOS]

# ``pct`` is a permutation mod 100, so each range predicate admits its
# fraction of rows *exactly*; 777 never occurs (selectivity 0)
SPECS = [
    ("sel_0.00", FilterSpec.eq("pct", 777)),
    ("sel_0.05", FilterSpec.range("pct", 0, 4)),
    ("sel_0.50", FilterSpec.range("pct", 0, 49)),
    ("sel_1.00", FilterSpec.range("pct", 0, 99)),
]


def _corpus(rng, n):
    c = rng.normal(size=(8, D)) * 4
    return (c[rng.integers(0, 8, n)]
            + rng.normal(size=(n, D))).astype(np.float32)


def _meta_for(rng, n):
    return MetadataTable({"pct": (rng.permutation(n) % 100).astype(np.int32)})


def _build(db, top, bottom, p, metadata=None):
    cfg = TwoLevelConfig(
        n_clusters=K, top=top, bottom=bottom, kmeans_iters=3,
        kmeans_minibatch=None, bucket_cap=CAP, tree_leaf=4,
        lsh_bits=32, pq_m=4,
    )
    return build_two_level(db, cfg, p=p, metadata=metadata)


def _oracle(q, db, ok, k):
    """Pure-numpy masked brute scan: stable top-k over inf-masked L2."""
    d = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    d = np.where(ok[None, :], d, np.inf)
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    dd = np.take_along_axis(d, idx, 1)
    return dd, np.where(np.isinf(dd), -1, idx)


# ---------------------------------------------------------------------------
# (a)-(c): every top x bottom combo, every selectivity, fresh + post-delta
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top,bottom", COMBOS)
def test_filtered_kernel_body_vs_reference(top, bottom, monkeypatch):
    rng = np.random.default_rng(700 + TOP_ALGOS.index(top) * 10
                                + BOTTOM_ALGOS.index(bottom))
    db = _corpus(rng, N)
    p = rng.dirichlet(np.full(N, 0.5)) if bottom == "qlbt" else None
    meta = _meta_for(rng, N)
    idx = _build(db, top, bottom, p, metadata=meta)
    mesh = make_mesh((1,), ("data",))
    kw = dict(k=TOPK, axes=("data",), nprobe_local=K, beam_width=8,
              headroom=1.5)
    be_r = ShardedSearchBackend(mesh, idx, **kw)
    be_p = ShardedSearchBackend(mesh, idx, **kw)
    q = _corpus(rng, NQ)

    def check(tag):
        alive = (np.ones(meta.n_rows, bool) if idx.alive is None
                 else np.asarray(idx.alive, bool))
        for name, fs in SPECS:
            df, i_f = be_r(q, filter_spec=fs)
            with pallas_kernels(monkeypatch):
                dp, ip = be_p(q, filter_spec=fs)
            assert np.array_equal(ip, i_f), (
                f"{top}/{bottom} [{tag} {name}]: kernel body filtered "
                f"ids diverged from the reference")
            if be_r.kind == "forest":
                np.testing.assert_allclose(dp, df, rtol=1e-5, atol=1e-5)
            else:
                assert np.array_equal(dp, df), (
                    f"{top}/{bottom} [{tag} {name}]: kernel body filtered "
                    f"distances diverged from the reference")
            ok = fs.mask(meta, alive.shape[0]) & alive
            real = i_f[i_f >= 0]
            assert ok[real].all(), (
                f"{top}/{bottom} [{tag} {name}]: returned an id the "
                f"predicate (or a tombstone) excludes")
            if name == "sel_0.00":
                assert np.all(i_f == -1) and np.all(np.isinf(df)), (
                    f"{top}/{bottom} [{tag}]: selectivity-0 must be the "
                    f"full (inf, -1) sentinel surface")
                assert not np.isnan(df).any()
        # selectivity 1.0 (a real predicate admitting every row) must be
        # bitwise-equal to the unfiltered call
        d0, i0 = be_r(q)
        d1, i1 = be_r(q, filter_spec=SPECS[-1][1])
        assert np.array_equal(d0, d1) and np.array_equal(i0, i1), (
            f"{top}/{bottom} [{tag}]: selectivity-1.0 filter changed "
            f"the unfiltered answer")

    check("fresh")

    # localized mutation -> ONE popped manifest -> delta apply on BOTH;
    # appended rows carry metadata, so they are filterable immediately
    b = int(np.argmax(idx.bucket_counts))
    dele = idx.bucket_ids[b][:5].copy()
    idx.delete_entities(dele)
    new = (idx.centroids[1][None, :]
           + 0.1 * rng.normal(size=(5, D))).astype(np.float32)
    idx.add_entities(new, metadata={"pct": np.full(5, 2, np.int32)})
    man = idx.pop_delta()
    stf = be_r.apply_updates(idx, delta=man)
    stp = be_p.apply_updates(idx, delta=man)
    assert stf["mode"] == stp["mode"] == "delta", (stf, stp)
    check("post-delta")
    # tombstoned rows stay dead under every filter
    for _, fs in SPECS:
        _, i_f = be_r(q, filter_spec=fs)
        assert not np.isin(i_f, dele).any(), (
            f"{top}/{bottom}: deleted id returned through a filtered "
            f"delta-path search")


# ---------------------------------------------------------------------------
# (d): the brute kind is exact vs the numpy oracle, fresh and post-delta
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_filtered_brute_exact_oracle(kernel, monkeypatch):
    if kernel == "pallas":
        # the whole test traces the scan through the kernel body
        with pallas_kernels(monkeypatch):
            _filtered_brute_exact_oracle(801)
    else:
        _filtered_brute_exact_oracle(800)


def _filtered_brute_exact_oracle(seed):
    rng = np.random.default_rng(seed)
    db = _corpus(rng, N)
    meta = _meta_for(rng, N)
    mesh = make_mesh((1,), ("data",))
    be = ShardedSearchBackend(
        mesh, db, k=TOPK, axes=("data",), headroom=1.5,
        metadata=meta, delta_max_fraction=1.0)
    q = _corpus(rng, NQ)
    compound = (FilterSpec.range("pct", 10, 80)
                & FilterSpec.isin("pct", tuple(range(0, 100, 3))))
    all_specs = SPECS + [("compound", compound)]

    def check(db_now, alive, tag):
        for name, fs in all_specs:
            d, i = be(q, filter_spec=fs)
            ok = fs.mask(meta, alive.shape[0]) & alive
            od, oi = _oracle(q, db_now, ok, TOPK)
            # ids are exact; distances match up to f32 accumulation
            # order (the kernel uses the expanded |q-x|^2 form)
            assert np.array_equal(i, oi), (
                f"brute [{tag} {name}]: filtered scan diverged from the "
                f"numpy oracle")
            assert np.array_equal(np.isinf(d), np.isinf(od))
            fin = np.isfinite(od)
            np.testing.assert_allclose(d[fin], od[fin], rtol=1e-4,
                                       atol=1e-4)

    check(db, np.ones(N, bool), "fresh")

    # tombstones + appended rows down the delta path, then re-check the
    # whole selectivity matrix against the oracle on the mutated corpus
    new = _corpus(rng, 16)
    db2 = np.concatenate([db, new])
    meta.append_rows({"pct": (np.arange(16) % 100).astype(np.int32)}, 16)
    tomb = np.arange(0, 60, 5).astype(np.int64)
    man = DeltaManifest(base_version=0, version=1, base_n=N, n=N + 16,
                        tombstones=tomb)
    st = be.apply_updates(db2, delta=man)
    assert st["mode"] == "delta", st
    alive2 = np.ones(N + 16, bool)
    alive2[tomb] = False
    check(db2, alive2, "post-delta")


# ---------------------------------------------------------------------------
# (e): lexical + hybrid modes vs their oracles, composed with filters
# ---------------------------------------------------------------------------


def test_lexical_and_hybrid_conformance():
    rng = np.random.default_rng(900)
    n, nv = 300, 60
    db = _corpus(rng, n)
    meta = MetadataTable(
        {"pct": (rng.permutation(n) % 100).astype(np.int32)})
    docs = [list(rng.integers(0, nv, rng.integers(3, 12)))
            for _ in range(n)]
    slabs = build_lexical_slabs(docs, nv)
    q = _corpus(rng, 6)
    qt, qw = query_operands(
        [list(rng.integers(0, nv, 5)) for _ in range(6)], slabs)
    mesh = make_mesh((1,), ("data",))
    kw = dict(k=TOPK, axes=("data",), headroom=1.5, metadata=meta,
              lexical=slabs, delta_max_fraction=1.0)
    be_r = ShardedSearchBackend(mesh, db, **kw)
    alive = np.ones(n, bool)
    fs = FilterSpec.range("pct", 0, 49)
    emask = fs.mask(meta, n)

    def lex_oracle(ok):
        bd = bm25_dists(slabs.terms, slabs.tf_sat,
                        np.asarray(qt), np.asarray(qw))
        bdm = np.where(ok[None, :], bd, np.inf)
        order = np.argsort(bdm, axis=1, kind="stable")[:, :TOPK]
        return np.take_along_axis(bdm, order, 1)

    def hyb_oracle(ok, alpha):
        d2 = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
        bd = bm25_dists(slabs.terms, slabs.tf_sat,
                        np.asarray(qt), np.asarray(qw))
        comb = np.where(ok[None, :],
                        alpha * d2 + (1.0 - alpha) * bd, np.inf)
        order = np.argsort(comb, axis=1, kind="stable")[:, :TOPK]
        return np.take_along_axis(comb, order, 1)

    # lexical: distances match the BM25 oracle
    dl, il = be_r(q, mode="lexical", q_terms=qt, q_weights=qw)
    assert np.allclose(dl, lex_oracle(alive), atol=1e-5)

    # hybrid across alphas: oracle-close; alpha is an operand, so no
    # alpha mints a new jit signature
    for alpha in (0.0, 0.3, 1.0):
        dh, ih = be_r(q, mode="hybrid", alpha=alpha,
                      q_terms=qt, q_weights=qw)
        assert np.allclose(dh, hyb_oracle(alive, alpha), atol=1e-4), (
            f"hybrid alpha={alpha} diverged from the numpy oracle")

    # filters compose with both modes (predicate mask ANDed into valid)
    for mode in ("lexical", "hybrid"):
        d, i = be_r(q, mode=mode, filter_spec=fs,
                    q_terms=qt, q_weights=qw)
        real = i[i >= 0]
        assert emask[real].all(), (
            f"{mode}+filter returned an excluded id")
        d0, i0 = be_r(q, mode=mode, filter_spec=FilterSpec.eq("pct", 777),
                      q_terms=qt, q_weights=qw)
        assert np.all(i0 == -1) and np.all(np.isinf(d0))
        assert not np.isnan(d0).any()

    # exactly one jitted callable per mode, regardless of how many
    # filter/alpha combinations were dispatched above
    _ = be_r(q, filter_spec=fs)          # semantic mode, filtered
    assert be_r.jit_cache_size() == 3, be_r.jit_cache_size()

    # delta path: appended docs join the lexical scan, under a filter
    # that admits them, and the slab scatter is delta-shaped
    new = _corpus(rng, 8)
    db2 = np.concatenate([db, new])
    slabs.append_docs([list(rng.integers(0, nv, 6)) for _ in range(8)])
    meta.append_rows({"pct": np.full(8, 2, np.int32)}, 8)
    man = DeltaManifest(base_version=0, version=1, base_n=n, n=n + 8)
    st = be_r.apply_updates(db2, delta=man)
    assert st["mode"] == "delta", st
    d, i = be_r(q, mode="lexical", filter_spec=fs,
                q_terms=qt, q_weights=qw)
    emask2 = fs.mask(meta, n + 8)
    real = i[i >= 0]
    assert emask2[real].all()
    bd = bm25_dists(slabs.terms, slabs.tf_sat,
                    np.asarray(qt), np.asarray(qw))
    bdm = np.where(emask2[None, :], bd, np.inf)
    order = np.argsort(bdm, axis=1, kind="stable")[:, :TOPK]
    assert np.allclose(d, np.take_along_axis(bdm, order, 1), atol=1e-5), (
        "post-delta filtered lexical scan diverged from the oracle")
    assert be_r.jit_cache_size() == 3, "delta apply minted a signature"


def test_mode_and_filter_validation():
    rng = np.random.default_rng(901)
    db = _corpus(rng, 64)
    meta = MetadataTable({"pct": np.zeros(64, np.int32)})
    mesh = make_mesh((1,), ("data",))
    be = ShardedSearchBackend(mesh, db, k=4, axes=("data",),
                              metadata=meta)
    q = _corpus(rng, 2)
    with pytest.raises(ValueError, match="mode"):
        be(q, mode="sparse")
    with pytest.raises(ValueError, match="lexical"):
        be(q, mode="lexical", q_terms=np.zeros((2, 4), np.int32),
           q_weights=np.zeros((2, 4), np.float32))
    with pytest.raises(KeyError, match="unknown metadata column"):
        be(q, filter_spec=FilterSpec.eq("nope", 1))
    with pytest.raises(ValueError, match="bad predicate"):
        FilterSpec((("gt", "pct", 3),))
    # an empty FilterSpec is the unfiltered path, bitwise
    d0, i0 = be(q)
    d1, i1 = be(q, filter_spec=FilterSpec())
    assert np.array_equal(d0, d1) and np.array_equal(i0, i1)


# ---------------------------------------------------------------------------
# (f): admission-cache key isolation + post-swap invalidation (regression:
# the key must fold in filter digest, mode, and alpha)
# ---------------------------------------------------------------------------


def test_cache_key_isolation_and_invalidation():
    from repro.adaptive import FrequencyAdmissionCache
    from repro.serve.cell import _opts_extra
    from repro.serve.engine import ServingEngine

    q = np.arange(8, dtype=np.float32)
    fs = FilterSpec.eq("pct", 1)
    # default options keep the historical key (extra == b"")
    assert _opts_extra(None, "semantic", 0.5) == b""
    k0 = FrequencyAdmissionCache.key_for(q)
    assert FrequencyAdmissionCache.key_for(
        q, _opts_extra(None, "semantic", 0.5)) == k0
    variants = {
        FrequencyAdmissionCache.key_for(q, _opts_extra(fs, "semantic", 0.5)),
        FrequencyAdmissionCache.key_for(
            q, _opts_extra(FilterSpec.eq("pct", 2), "semantic", 0.5)),
        FrequencyAdmissionCache.key_for(q, _opts_extra(None, "hybrid", 0.5)),
        FrequencyAdmissionCache.key_for(q, _opts_extra(None, "hybrid", 0.7)),
        FrequencyAdmissionCache.key_for(q, _opts_extra(fs, "hybrid", 0.5)),
        k0,
    }
    assert len(variants) == 6, "filter/mode/alpha variants collided"

    # end-to-end: filtered and unfiltered answers for the SAME query are
    # cached separately, both hit on re-ask, and a swap drops both
    rng = np.random.default_rng(902)
    n = 200
    db = _corpus(rng, n)
    meta = MetadataTable(
        {"pct": (rng.permutation(n) % 100).astype(np.int32)})
    mesh = make_mesh((1,), ("data",))
    be = ShardedSearchBackend(mesh, db, k=TOPK, axes=("data",),
                              headroom=1.5, metadata=meta,
                              delta_max_fraction=1.0)
    cache = FrequencyAdmissionCache(capacity=64)
    eng = ServingEngine(be, cache=cache, max_wait_ms=0.5)
    try:
        fs = FilterSpec.range("pct", 0, 4)
        query = db[0].copy()
        d_u, i_u = eng.search(query, timeout=30.0)
        d_f, i_f = eng.search(query, timeout=30.0, filter=fs)
        emask = fs.mask(meta, n)
        assert not np.array_equal(i_u, i_f)
        assert emask[i_f[i_f >= 0]].all()
        h0 = cache.hits
        d_u2, i_u2 = eng.search(query, timeout=30.0)
        d_f2, i_f2 = eng.search(query, timeout=30.0, filter=fs)
        assert cache.hits >= h0 + 2, "variant keys missed the cache"
        assert np.array_equal(i_u, i_u2) and np.array_equal(i_f, i_f2)
        assert np.array_equal(d_u, d_u2) and np.array_equal(d_f, d_f2)

        # delete the filtered answer's best row; after the swap neither
        # the filtered nor the unfiltered cached variant may resurface it
        victim = int(i_f[0])
        db2 = db.copy()
        man = DeltaManifest(base_version=0, version=1, base_n=n, n=n,
                            tombstones=np.asarray([victim], np.int64))
        eng.apply_updates(db2, delta=man)
        _, i_u3 = eng.search(query, timeout=30.0)
        _, i_f3 = eng.search(query, timeout=30.0, filter=fs)
        assert victim not in i_u3 and victim not in i_f3, (
            "cache served a deleted entity after apply_updates")
    finally:
        eng.close()
