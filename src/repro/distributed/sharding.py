"""Sharded execution: ShardPlan (symbolic axes) + the sharded ANN search.

Two halves, one subsystem:

``ShardPlan`` — models annotate params/activations with *roles* — "dp"
(batch), "fsdp" (param gather), "tp" (tensor), "ep" (expert) — and the
launcher binds roles to concrete mesh axes:

  single-pod (16,16) ("data","model"): dp=(data,) fsdp=(data,) tp=(model,)
                                       ep=(data,model)
  multi-pod (2,16,16) (+pod):          dp=(pod,data) fsdp=(pod,data) ...

so the same model code lowers on any mesh.  With no mesh bound, ``p()``
returns fully-replicated specs and ``constrain`` is a no-op — the path unit
tests take.

Sharded search — the paper's two-level structure gains one more level: the
mesh.  Buckets (and their centroids) are sharded across chips; each chip
runs the paper's top+bottom search over its local shard; a tiny
``all_gather`` of per-chip top-k (k * 8 bytes per query) merges globally.
The collective term is O(devices * B * k) bytes — independent of corpus
size, which is what makes the approach scale-out friendly (EXPERIMENTS.md
§Roofline, ann rows).  Three bottom levels are distributed here:

  * ``sharded_brute_search``  — exact scan, db row-sharded;
  * ``sharded_ivf_search``    — two-level brute bottom, buckets sharded;
  * ``sharded_forest_search`` — two-level tree/QLBT bottom: each shard
    holds a slice of the concatenated per-bucket forest and descends it
    locally before the global merge.

Every entry point takes ``query_axes`` to additionally shard the *query*
batch over a second mesh axis (corpus over one, queries over the other),
so both B and N scale; the merge all-gathers only over the corpus axes and
results come back sharded over the query axes.

All collectives are built with ``jax.shard_map`` so the communication
pattern is explicit in the lowered HLO.

Online mutation: every sharder here can re-place a *mutated* index into
previously recorded array shapes — ``forest_shard_shapes`` +
``shard_forest(shapes=...)`` for the forest, a reserved row grid with an
explicit ``valid`` operand for the brute scan, a reserved bucket cap for
IVF — so :class:`repro.distributed.backend.ShardedSearchBackend` serves
through ``add_entities``/``delete_entities``/``rebalance`` without
re-jitting (see the README's "Online mutation" section).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.brute import batched_l2sq, pairwise_l2sq
from repro.kernels import ops as kernel_ops

__all__ = [
    "ShardPlan", "SINGLE_POD_PLAN", "MULTI_POD_PLAN", "LOCAL_PLAN",
    "sharded_brute_search", "sharded_ivf_search", "sharded_forest_search",
    "make_sharded_brute_fn", "make_sharded_ivf_fn", "make_sharded_forest_fn",
    "make_sharded_lexical_fn", "make_sharded_hybrid_fn",
    "shard_forest", "forest_shard_shapes", "ForestShardShapes",
    "slice_forest_delta", "slice_ivf_delta",
]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    dp: tuple = ()
    fsdp: tuple = ()
    tp: tuple = ()
    ep: tuple = ()
    pp: tuple = ()      # pod-parallel remainder of dp once ep covers a pod
    mesh: Any = None

    def resolve(self, sym) -> Optional[tuple]:
        """role symbol | tuple of roles | None -> mesh-axis tuple | None."""
        if sym is None:
            return None
        if isinstance(sym, tuple):
            axes: list = []
            for s in sym:
                r = self.resolve(s)
                if r:
                    axes.extend(r)
            return tuple(dict.fromkeys(axes)) or None
        axes = getattr(self, sym)
        return tuple(axes) or None

    def p(self, *dims) -> P:
        return P(*[self.resolve(d) for d in dims])

    def constrain(self, x, *dims):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.p(*dims))
        )

    def axis_size(self, role: str) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in getattr(self, role):
            n *= self.mesh.shape[a]
        return n

    def size_of(self, sym) -> int:
        """Device count along a role symbol or tuple of roles."""
        if sym is None:
            return 1
        if isinstance(sym, tuple):
            n = 1
            for s in sym:
                n *= self.size_of(s)
            return n
        return self.axis_size(sym)

    def div_p(self, shape, *dims) -> P:
        """Like ``p`` but drops any role whose device count does not divide
        the corresponding dim (small/odd recsys layers stay replicated)."""
        parts = []
        for size, d in zip(shape, dims):
            parts.append(d if d and size % max(self.size_of(d), 1) == 0
                         else None)
        return self.p(*parts)

    def with_mesh(self, mesh) -> "ShardPlan":
        return dataclasses.replace(self, mesh=mesh)


LOCAL_PLAN = ShardPlan()

SINGLE_POD_PLAN = ShardPlan(
    dp=("data",), fsdp=("data",), tp=("model",), ep=("data", "model")
)

# ep stays within a pod (("data","model") = 256-way): experts are replicated
# across pods so the MoE all-to-all never crosses the slow inter-pod links;
# pods combine through the data-parallel gradient reduction only.  The
# dispatch-group dim stays sharded over "pod" (pp) during expert compute —
# without it, a P(None, ep, ...) constraint replicates every pod's tokens
# into both pods (observed 17 TB of cross-pod all-gather).
MULTI_POD_PLAN = ShardPlan(
    dp=("pod", "data"), fsdp=("pod", "data"), tp=("model",),
    ep=("data", "model"), pp=("pod",),
)


# ---------------------------------------------------------------------------
# Sharded search
# ---------------------------------------------------------------------------


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _q_spec(query_axes) -> P:
    return P(tuple(query_axes), None) if query_axes else P(None, None)


def _check_disjoint(axes, query_axes):
    """Corpus and query axes must not overlap: the merge all-gathers over
    the corpus axes, and a shared axis would top-k-merge results belonging
    to *different* queries — silently wrong, so refuse up front."""
    overlap = set(axes) & set(query_axes)
    if overlap:
        raise ValueError(
            f"query_axes {tuple(query_axes)} overlap corpus axes "
            f"{tuple(axes)} on {sorted(overlap)}; pass disjoint axes, e.g. "
            "axes=('data',), query_axes=('model',)")


def _brute_device_arrays(db, n_dev, rows=None, alive=None):
    """Zero-pad db rows to the shard grid.  Pads (and tombstoned rows, via
    ``alive``) are masked by an explicit per-row *valid* array rather than
    a row count baked into the jitted program, so a mutated corpus can be
    re-placed without re-jitting as long as the grid fits.  Returns host
    (numpy) arrays — they go straight to their sharded placement, never
    through the default device — as (padded db, valid mask, rows per
    shard, real rows)."""
    db = np.asarray(db, np.float32)
    n = db.shape[0]
    if rows is None:
        rows = -(-n // n_dev)
    if rows * n_dev < n:
        raise ValueError(
            f"corpus has {n} rows but the shard grid holds only "
            f"{rows * n_dev}; rebuild the backend (or raise headroom)")
    valid = np.arange(rows * n_dev) < n
    if alive is not None:
        valid[:n] &= np.asarray(alive, bool)
    return np.pad(db, ((0, rows * n_dev - n), (0, 0))), valid, rows, n


def _merge_gathered(gd, gi, k):
    """(S, B, k) per-shard results -> merged (B, k)."""
    s, b, kk = gd.shape
    cat_d = jnp.moveaxis(gd, 0, 1).reshape(b, s * kk)
    cat_i = jnp.moveaxis(gi, 0, 1).reshape(b, s * kk)
    neg, sel = jax.lax.top_k(-cat_d, k)
    ids = jnp.take_along_axis(cat_i, sel, axis=1)
    return -neg, jnp.where(jnp.isinf(-neg), -1, ids)


def make_sharded_brute_fn(mesh, axes: tuple, k: int, shard_rows: int,
                          query_axes: tuple = (), *, fused: bool = True,
                          precision: str = "f32"):
    """Exact distributed search: db row-sharded over ``axes``; queries
    optionally batch-sharded over ``query_axes``.

    Pad rows (db zero-padded up to the shard grid) and tombstoned rows are
    masked by the explicit ``valid`` operand — never by inf-valued vectors,
    whose distances evaluate to ``inf - inf = NaN`` and can outrank real
    candidates in XLA's top_k.  ``valid`` being data (not a baked-in row
    count) is what lets ``ShardedSearchBackend.apply_updates`` serve
    through corpus mutations without re-jitting.

    ``fused=True`` (default) routes the per-shard scan through
    ``kernels.ops.l2_topk_op`` — on TPU the Pallas streaming kernel, which
    never materializes the local ``(B, rows)`` distance matrix; on CPU the
    jnp oracle whose ops are literally the unfused path's, so results are
    bitwise-identical either way.  ``precision="int8"`` (fused only)
    switches the operand set to per-row-scaled int8 codes — the callable
    then takes ``(codes, scales, valid, q)``.
    """
    _check_disjoint(axes, query_axes)
    if precision not in ("f32", "int8"):
        raise ValueError(f"precision must be 'f32' or 'int8', "
                         f"got {precision!r}")
    if precision == "int8" and not fused:
        raise ValueError("precision='int8' is a fused-kernel feature; "
                         "pass fused=True")
    k_loc = min(k, shard_rows)   # a shard may hold fewer rows than k

    def _finish_local(ld, li, lin):
        # shard-local slot ids -> global row ids; the (inf, -1) kernel
        # sentinel must stay -1 rather than alias shard 0's rows
        li = jnp.where(li >= 0, li + lin * shard_rows, -1).astype(jnp.int32)
        if k_loc < k:
            ld = jnp.pad(ld, ((0, 0), (0, k - k_loc)),
                         constant_values=jnp.inf)
            li = jnp.pad(li, ((0, 0), (0, k - k_loc)), constant_values=-1)
        gd = jax.lax.all_gather(ld, axes, tiled=False)     # (S, B, k)
        gi = jax.lax.all_gather(li, axes, tiled=False)
        return _merge_gathered(gd, gi, k)

    def local(db_shard, valid_shard, q):
        lin = jax.lax.axis_index(axes)                     # flattened index
        if fused:
            ld, li = kernel_ops.l2_topk_op(q, db_shard, k_loc,
                                           valid=valid_shard)
        else:
            d2 = pairwise_l2sq(q, db_shard)                # (B, rows)
            d2 = jnp.where(valid_shard[None, :], d2, jnp.inf)
            neg, li = jax.lax.top_k(-d2, k_loc)
            ld = -neg
        return _finish_local(ld, li, lin)

    def local_int8(codes_shard, scales_shard, valid_shard, q):
        lin = jax.lax.axis_index(axes)
        ld, li = kernel_ops.l2_topk_int8_op(
            q, codes_shard, scales_shard, k_loc, valid=valid_shard)
        return _finish_local(ld, li, lin)

    qs = _q_spec(query_axes)
    if precision == "int8":
        return shard_map(
            local_int8, mesh=mesh,
            in_specs=(P(tuple(axes), None), P(tuple(axes)),
                      P(tuple(axes)), qs),
            out_specs=(qs, qs),
            check_vma=False,
        )
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(tuple(axes)), qs),
        out_specs=(qs, qs),
        check_vma=False,   # merge all-gathers over the corpus axes only
    )


def _brute_int8_device_arrays(db, n_dev, rows=None, alive=None):
    """int8 counterpart of ``_brute_device_arrays``: per-row symmetric
    quantization (``kernels.ops.quantize_rows_int8``) before padding, so
    pad rows are zero codes with scale 1.0 (dequantize to exact zero) and
    are masked by ``valid`` like every other dead row.  Returns
    (codes, scales, valid, rows per shard, real rows)."""
    db = np.asarray(db, np.float32)
    n = db.shape[0]
    if rows is None:
        rows = -(-n // n_dev)
    if rows * n_dev < n:
        raise ValueError(
            f"corpus has {n} rows but the shard grid holds only "
            f"{rows * n_dev}; rebuild the backend (or raise headroom)")
    codes, scales = kernel_ops.quantize_rows_int8(db)
    pad = rows * n_dev - n
    codes = np.pad(codes, ((0, pad), (0, 0)))
    scales = np.pad(scales, (0, pad), constant_values=1.0)
    valid = np.arange(rows * n_dev) < n
    if alive is not None:
        valid[:n] &= np.asarray(alive, bool)
    return codes, scales, valid, rows, n


def _pad_queries(mesh, queries, query_axes):
    q = np.asarray(queries, np.float32)
    B = q.shape[0]
    n_q = _axes_size(mesh, query_axes) if query_axes else 1
    Bp = -(-B // n_q) * n_q
    if Bp > B:
        q = np.pad(q, ((0, Bp - B), (0, 0)))
    return q, B


def _pad_term_queries(mesh, q_terms, q_weights, query_axes):
    """Batch-pad the lexical query operands to the query-axis grid.

    Pad rows get term id -1 (never matches a slab slot) and weight 0, so
    the padded queries score nothing and are trimmed after the merge —
    same contract as :func:`_pad_queries` for dense queries."""
    qt = np.asarray(q_terms, np.int32)
    qw = np.asarray(q_weights, np.float32)
    B = qt.shape[0]
    n_q = _axes_size(mesh, query_axes) if query_axes else 1
    Bp = -(-B // n_q) * n_q
    if Bp > B:
        qt = np.pad(qt, ((0, Bp - B), (0, 0)), constant_values=-1)
        qw = np.pad(qw, ((0, Bp - B), (0, 0)))
    return qt, qw, B


def sharded_brute_search(mesh, db, queries, k=10, axes=("data", "model"),
                         query_axes=(), fused=True, precision="f32"):
    """Host entry: shards db rows over ``axes`` and runs the distributed
    scan; ``query_axes`` shards the batch dim over a *disjoint* axis set.
    ``fused``/``precision`` select the kernel path (see
    :func:`make_sharded_brute_fn`)."""
    n_dev = _axes_size(mesh, axes)
    q, B = _pad_queries(mesh, queries, query_axes)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    if precision == "int8":
        codes, scales, valid, rows, _ = _brute_int8_device_arrays(db, n_dev)
        fn = make_sharded_brute_fn(mesh, tuple(axes), k, rows,
                                   tuple(query_axes), fused=fused,
                                   precision=precision)
        with mesh:
            d, i = fn(put(codes, P(tuple(axes), None)),
                      put(scales, P(tuple(axes))),
                      put(valid, P(tuple(axes))),
                      put(q, _q_spec(query_axes)))
    else:
        dbp, valid, rows, _ = _brute_device_arrays(db, n_dev)
        fn = make_sharded_brute_fn(mesh, tuple(axes), k, rows,
                                   tuple(query_axes), fused=fused,
                                   precision=precision)
        with mesh:
            d, i = fn(put(dbp, P(tuple(axes), None)),
                      put(valid, P(tuple(axes))),
                      put(q, _q_spec(query_axes)))
    d, i = jax.device_get((d, i))
    return np.asarray(d)[:B], np.asarray(i)[:B]


def _lexical_device_arrays(terms, tf_sat, n_dev, rows=None, alive=None):
    """Postings-slab counterpart of ``_brute_device_arrays``: term rows
    padded with -1 (no term id 0 aliasing), tf rows with zeros; pads and
    tombstones are masked by the same explicit ``valid`` operand.
    Returns (padded terms, padded tf_sat, valid, rows per shard, n)."""
    t = np.asarray(terms, np.int32)
    f = np.asarray(tf_sat, np.float32)
    n = t.shape[0]
    if rows is None:
        rows = -(-n // n_dev)
    if rows * n_dev < n:
        raise ValueError(
            f"postings have {n} rows but the shard grid holds only "
            f"{rows * n_dev}; rebuild the backend (or raise headroom)")
    pad = rows * n_dev - n
    tp = np.pad(t, ((0, pad), (0, 0)), constant_values=-1)
    fp = np.pad(f, ((0, pad), (0, 0)))
    valid = np.arange(rows * n_dev) < n
    if alive is not None:
        valid[:n] &= np.asarray(alive, bool)
    return tp, fp, valid, rows, n


def make_sharded_lexical_fn(mesh, axes: tuple, k: int, shard_rows: int,
                            query_axes: tuple = (), *, fused: bool = True):
    """Distributed BM25 lexical scan: postings slabs row-sharded over
    ``axes`` — the brute layout with term/tf slabs in place of vectors.
    The callable takes ``(terms, tf_sat, valid, q_terms, q_weights)``;
    filters and tombstones compose through ``valid`` exactly as in the
    brute scan, so a filtered call reuses the unfiltered signature.
    """
    from repro.kernels.ref import bm25_dists_ref

    _check_disjoint(axes, query_axes)
    k_loc = min(k, shard_rows)

    def _finish_local(ld, li, lin):
        li = jnp.where(li >= 0, li + lin * shard_rows, -1).astype(jnp.int32)
        if k_loc < k:
            ld = jnp.pad(ld, ((0, 0), (0, k - k_loc)),
                         constant_values=jnp.inf)
            li = jnp.pad(li, ((0, 0), (0, k - k_loc)), constant_values=-1)
        gd = jax.lax.all_gather(ld, axes, tiled=False)
        gi = jax.lax.all_gather(li, axes, tiled=False)
        return _merge_gathered(gd, gi, k)

    def local(terms_shard, tf_shard, valid_shard, qt, qw):
        lin = jax.lax.axis_index(axes)
        if fused:
            ld, li = kernel_ops.bm25_topk_op(
                qt, qw, terms_shard, tf_shard, k_loc, valid=valid_shard)
        else:
            dist = bm25_dists_ref(qt, qw, terms_shard, tf_shard)
            dist = jnp.where(valid_shard[None, :], dist, jnp.inf)
            neg, li = jax.lax.top_k(-dist, k_loc)
            ld = -neg
        return _finish_local(ld, li, lin)

    qs = _q_spec(query_axes)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(tuple(axes), None),
                  P(tuple(axes)), qs, qs),
        out_specs=(qs, qs),
        check_vma=False,   # merge all-gathers over the corpus axes only
    )


def make_sharded_hybrid_fn(mesh, axes: tuple, k: int, shard_rows: int,
                           query_axes: tuple = (), *, fused: bool = True):
    """Distributed hybrid scan: semantic L2 and BM25 fused per shard as
    ``alpha * l2sq - (1 - alpha) * bm25``.

    The callable takes ``(db, terms, tf_sat, valid, q, q_terms,
    q_weights, alpha)``; ``alpha`` is a replicated (1, 1) f32 *operand*
    — sweeping the blend mints no new executables (the recompile gate's
    ``filtered-sharded-search`` entry covers this).
    """
    from repro.kernels.ref import bm25_dists_ref

    _check_disjoint(axes, query_axes)
    k_loc = min(k, shard_rows)

    def _finish_local(ld, li, lin):
        li = jnp.where(li >= 0, li + lin * shard_rows, -1).astype(jnp.int32)
        if k_loc < k:
            ld = jnp.pad(ld, ((0, 0), (0, k - k_loc)),
                         constant_values=jnp.inf)
            li = jnp.pad(li, ((0, 0), (0, k - k_loc)), constant_values=-1)
        gd = jax.lax.all_gather(ld, axes, tiled=False)
        gi = jax.lax.all_gather(li, axes, tiled=False)
        return _merge_gathered(gd, gi, k)

    def local(db_shard, terms_shard, tf_shard, valid_shard,
              q, qt, qw, alpha):
        lin = jax.lax.axis_index(axes)
        if fused:
            ld, li = kernel_ops.hybrid_topk_op(
                q, db_shard, qt, qw, terms_shard, tf_shard, alpha, k_loc,
                valid=valid_shard)
        else:
            d2 = pairwise_l2sq(q, db_shard)
            score = -bm25_dists_ref(qt, qw, terms_shard, tf_shard)
            a = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
            dist = a * d2 - (1.0 - a) * score
            dist = jnp.where(valid_shard[None, :], dist, jnp.inf)
            neg, li = jax.lax.top_k(-dist, k_loc)
            ld = -neg
        return _finish_local(ld, li, lin)

    qs = _q_spec(query_axes)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(tuple(axes), None),
                  P(tuple(axes), None), P(tuple(axes)),
                  qs, qs, qs, P(None, None)),
        out_specs=(qs, qs),
        check_vma=False,   # merge all-gathers over the corpus axes only
    )


def make_sharded_ivf_fn(mesh, axes: tuple, k: int, nprobe_local: int,
                        buckets_per_shard: int, n_buckets: int,
                        query_axes: tuple = (), *, fused: bool = True):
    """Distributed two-level, brute bottom: centroids + padded buckets
    sharded over the mesh.

    Each chip: (1) scores its local centroids, (2) probes its local
    ``nprobe_local`` best buckets, (3) contributes its local top-k to the
    global all-gather merge.  Global nprobe = nprobe_local * n_shards —
    probing is *wider* than single-chip at equal latency, a scale-out win
    the paper's single-device protocol cannot reach.  Pad centroids (zero
    vectors beyond ``n_buckets``) are masked by global bucket index.
    """

    _check_disjoint(axes, query_axes)
    nprobe_local = min(nprobe_local, buckets_per_shard)

    def local(cents, bucket_ids, bucket_vecs, q):
        # cents: (Kloc, d); bucket_ids: (Kloc, cap); bucket_vecs (Kloc, cap, d)
        lin = jax.lax.axis_index(axes)
        gbucket = lin * buckets_per_shard + jnp.arange(
            buckets_per_shard, dtype=jnp.int32)
        d2c = pairwise_l2sq(q, cents)                      # (B, Kloc)
        d2c = jnp.where(gbucket[None, :] < n_buckets, d2c, jnp.inf)
        _, probe = jax.lax.top_k(-d2c, nprobe_local)       # (B, np)

        def scan_probe(carry, j):
            best_d, best_i = carry
            bsel = probe[:, j]                             # (B,)
            ids = bucket_ids[bsel]                         # (B, cap)
            vecs = bucket_vecs[bsel]                       # (B, cap, d)
            if fused:
                # distance + merge in one op (Pallas candidate kernel on
                # TPU; the same-ops jnp oracle on CPU) — the probe chain
                # carries the running best through the kernel
                return kernel_ops.candidate_topk_op(
                    q, vecs, ids, k, best_d=best_d, best_i=best_i), None
            d2 = batched_l2sq(vecs, q)
            d2 = jnp.where(ids >= 0, d2, jnp.inf)
            cat_d = jnp.concatenate([best_d, d2], axis=1)
            cat_i = jnp.concatenate([best_i, ids], axis=1)
            neg, sel = jax.lax.top_k(-cat_d, k)
            return (-neg, jnp.take_along_axis(cat_i, sel, 1)), None

        B = q.shape[0]
        init = (jnp.full((B, k), jnp.inf, jnp.float32),
                jnp.full((B, k), -1, jnp.int32))
        (ld, li), _ = jax.lax.scan(scan_probe, init,
                                   jnp.arange(nprobe_local, dtype=jnp.int32))
        gd = jax.lax.all_gather(ld, axes, tiled=False)
        gi = jax.lax.all_gather(li, axes, tiled=False)
        return _merge_gathered(gd, gi, k)

    qs = _q_spec(query_axes)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(tuple(axes), None),
                  P(tuple(axes), None, None), qs),
        out_specs=(qs, qs),
        check_vma=False,   # merge all-gathers over the corpus axes only
    )


def _bucket_width(width: int, headroom: float = 1.0, dtype=np.float32) -> int:
    """Bucket width to reserve for ``width`` rows with ``headroom``: rounded
    up to the sublane tile of ``dtype`` (8 rows of 32-bit data).  At an
    unaligned width the TPU compiler lays the ``(Kloc, cap, d)`` bucket
    tensor out cap-major to skip the padding, and the probe loop then
    re-lays the whole tensor out row-major on every call."""
    sublane = 8 * 4 // np.dtype(dtype).itemsize
    return -(-int(np.ceil(width * headroom)) // sublane) * sublane


def _ivf_device_arrays(index, n_dev, cap=None):
    """Pad a built TwoLevelIndex's centroid/bucket tables to the shard grid
    (zero vectors, -1 ids — pads are masked by index, never by inf).
    ``cap`` pads the bucket width beyond the index's own (update headroom:
    a mutated index re-places into the same shapes, so the jitted search
    is reused); by default the index's width rounded up to the sublane
    tile (:func:`_bucket_width`).  The bucket gather runs on the host: the
    tensor is several times the corpus (deep-10m: ~10 GB) and goes
    straight to its shards."""
    K, cap_now = index.bucket_ids.shape
    if cap is None:
        cap = _bucket_width(cap_now)
    if cap < cap_now:
        raise ValueError(
            f"bucket cap grew to {cap_now} > reserved {cap}; rebuild the "
            f"backend (or raise headroom)")
    Kp = -(-K // n_dev) * n_dev
    pad = Kp - K
    cents = np.pad(np.asarray(index.centroids, np.float32),
                   ((0, pad), (0, 0)))
    bids = np.pad(np.asarray(index.bucket_ids, np.int32),
                  ((0, pad), (0, cap - cap_now)), constant_values=-1)
    bvecs = np.asarray(index.db, np.float32)[np.maximum(bids, 0)]
    bvecs[bids < 0] = 0.0
    return cents, bids, bvecs, Kp


def sharded_ivf_search(mesh, index, queries, k=10, nprobe_local=2,
                       axes=("data", "model"), query_axes=(), fused=True):
    """Host entry: shards a built TwoLevelIndex (brute bottom) over the
    mesh.  ``index.bucket_ids`` keeps *global* entity ids, so the merged
    result ids are directly comparable with the single-chip index."""
    n_dev = _axes_size(mesh, axes)
    K = index.bucket_ids.shape[0]
    cents, bids, bvecs, Kp = _ivf_device_arrays(index, n_dev)
    fn = make_sharded_ivf_fn(mesh, tuple(axes), k, nprobe_local,
                             Kp // n_dev, K, tuple(query_axes), fused=fused)
    q, B = _pad_queries(mesh, queries, query_axes)
    with mesh:
        put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
        d, i = fn(
            put(cents, P(tuple(axes), None)),
            put(bids, P(tuple(axes), None)),
            put(bvecs, P(tuple(axes), None, None)),
            put(q, _q_spec(query_axes)),
        )
    d, i = jax.device_get((d, i))
    return np.asarray(d)[:B], np.asarray(i)[:B]


# ---------------------------------------------------------------------------
# Sharded tree/QLBT forest bottom level
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ForestShardShapes:
    """Fixed per-shard array shapes for a sliced forest.

    Recorded at backend construction (optionally with headroom) and
    re-applied by :meth:`ShardedSearchBackend.apply_updates`: a mutated
    index re-slices into the *same* shapes, so the jitted shard_map search
    keeps its compile cache across the whole index lifecycle.

    Two layouts share this record:

    * **packed** (``node_slab == 0``): each shard's buckets are packed
      back-to-back, minimal padding — the host entry points' layout.
    * **slab** (``node_slab > 0``): every bucket owns a fixed
      ``node_slab``-row node window and ``leaf_slab``-row leaf window at
      ``slot * slab``, so one bucket's rebuilt tree overwrites only its
      own slabs.  This is what makes *delta shipping* possible — a dirty
      bucket is a fixed-shape payload scattered in place on device — at
      the cost of padding every bucket to the largest tree
      (``nodes == kloc * node_slab``).
    """
    n_dev: int
    kloc: int       # buckets per shard
    cap: int        # bucket pad width
    nodes: int      # node-table rows per shard (excluding the dead node)
    leaves: int     # leaf-table rows per shard
    leaf_sz: int    # leaf width (entities per leaf row)
    max_depth: int  # bound on descent steps
    node_slab: int = 0   # slab layout: node rows reserved per bucket
    leaf_slab: int = 0   # slab layout: leaf rows reserved per bucket


def _forest_slices(index, n_dev: int):
    """Per-shard (b0, b1, N0, N1, L0, L1) bucket/node/leaf windows."""
    f = index.forest
    if f is None:
        raise ValueError("index has no forest (bottom must be tree/qlbt)")
    K = index.bucket_ids.shape[0]
    Kloc = -(-K // n_dev)
    leaf_row = np.asarray(f.arrays["leaf_row"])
    roots = np.asarray(f.roots, dtype=np.int64)
    n_nodes = leaf_row.shape[0]
    bounds = np.concatenate([roots, [n_nodes]])
    slices = []
    for s in range(n_dev):
        b0 = min(s * Kloc, K)
        b1 = min(b0 + Kloc, K)
        N0 = int(bounds[b0]) if b0 < K else n_nodes
        N1 = int(bounds[b1]) if b0 < K else n_nodes
        lr = leaf_row[N0:N1]
        rows = lr[lr >= 0]
        L0 = int(rows.min()) if rows.size else 0
        L1 = int(rows.max()) + 1 if rows.size else 0
        if rows.size not in (0, L1 - L0):
            raise ValueError(
                f"shard {s}: leaf rows not contiguous ({rows.size} rows in "
                f"window [{L0}, {L1})); _build_forest concatenation order "
                "changed?")
        slices.append((b0, b1, N0, N1, L0, L1))
    return slices, Kloc


def _bucket_windows(index):
    """Per-bucket (N0, N1, L0, L1) node/leaf windows in the concatenated
    forest (bucket ``b`` owns nodes ``[roots[b], roots[b+1])`` and the
    contiguous leaf rows its own tree contributed)."""
    f = index.forest
    if f is None:
        raise ValueError("index has no forest (bottom must be tree/qlbt)")
    K = index.bucket_ids.shape[0]
    leaf_row = np.asarray(f.arrays["leaf_row"])
    roots = np.asarray(f.roots, dtype=np.int64)
    bounds = np.concatenate([roots, [leaf_row.shape[0]]])
    windows = []
    for b in range(K):
        N0, N1 = int(bounds[b]), int(bounds[b + 1])
        lr = leaf_row[N0:N1]
        rows = lr[lr >= 0]
        L0 = int(rows.min()) if rows.size else 0
        L1 = int(rows.max()) + 1 if rows.size else 0
        if rows.size not in (0, L1 - L0):
            raise ValueError(
                f"bucket {b}: leaf rows not contiguous; "
                "_build_forest concatenation order changed?")
        windows.append((N0, N1, L0, L1))
    return windows


def forest_shard_shapes(index, n_dev: int, headroom: float = 1.0,
                        layout: str = "packed") -> ForestShardShapes:
    """Measure the natural per-shard shapes; ``headroom`` > 1 reserves
    room for post-mutation growth (bigger buckets after adds, deeper or
    wider trees after dirty-bucket rebuilds).

    ``layout="slab"`` reserves a fixed node/leaf slab *per bucket*
    (``headroom`` scales the slab against the current largest bucket
    tree) — the delta-shipping layout; see :class:`ForestShardShapes`.
    """
    f = index.forest
    cap = index.bucket_ids.shape[1]
    leaf_sz = np.asarray(f.arrays["leaf_entities"]).shape[1] if f else 0
    grow = lambda x: int(np.ceil(x * headroom))
    extra_depth = 8 if headroom > 1.0 else 0
    if layout == "slab":
        windows = _bucket_windows(index)
        K = index.bucket_ids.shape[0]
        Kloc = -(-K // n_dev)
        node_slab = grow(max(max((N1 - N0 for N0, N1, _, _ in windows),
                                 default=0), 1))
        leaf_slab = grow(max(max((L1 - L0 for *_, L0, L1 in windows),
                                 default=0), 1))
        return ForestShardShapes(
            n_dev=n_dev, kloc=Kloc, cap=grow(cap),
            nodes=Kloc * node_slab, leaves=Kloc * leaf_slab,
            leaf_sz=leaf_sz, max_depth=f.max_depth + extra_depth,
            node_slab=node_slab, leaf_slab=leaf_slab,
        )
    if layout != "packed":
        raise ValueError(f"layout must be 'packed' or 'slab', got {layout!r}")
    slices, Kloc = _forest_slices(index, n_dev)
    maxN = max(max((N1 - N0 for _, _, N0, N1, _, _ in slices), default=0), 1)
    maxL = max(max((L1 - L0 for *_, L0, L1 in slices), default=0), 1)
    return ForestShardShapes(
        n_dev=n_dev, kloc=Kloc, cap=grow(cap), nodes=grow(maxN),
        leaves=grow(maxL), leaf_sz=leaf_sz,
        max_depth=f.max_depth + extra_depth,
    )


def shard_forest(index, n_dev: int, *,
                 shapes: Optional[ForestShardShapes] = None) -> dict:
    """Slice a built forest index into ``n_dev`` equal-shape shards.

    The two-level build concatenates per-bucket trees into one node table
    (``two_level._build_forest``); bucket ``b`` owns node range
    ``[roots[b], roots[b+1])`` and a contiguous run of leaf-table rows.
    Each shard takes a contiguous block of buckets, re-bases node/leaf
    offsets, and remaps leaf entity ids from *global* entity ids to local
    *bucket-slot* ids (``bucket_row * cap + col``) so the rerank gathers
    from the shard's own ``(Kloc, cap, d)`` vector tile — corpus memory
    stays sharded.  One extra dead node per shard backs padded bucket
    roots.  Returns host (numpy) arrays stacked on a leading shard dim.

    ``shapes`` pads every shard to the given fixed sizes (raising if the
    forest outgrew them) so re-slicing a *mutated* index produces arrays
    of identical shape — the no-re-jit update path.  Deleted entities are
    naturally dropped: they are absent from ``bucket_ids``, so their leaf
    slots remap to -1.

    A ``shapes`` with ``node_slab > 0`` switches to the *slab* layout
    (every bucket at a fixed per-slot window — the delta-shipping
    layout); the two layouts produce identical search results, they only
    differ in padding placement.
    """
    if shapes is not None and shapes.node_slab > 0:
        return _shard_forest_slab(index, shapes)
    slices, Kloc = _forest_slices(index, n_dev)
    f = index.forest
    K, cap_now = index.bucket_ids.shape
    arrays = {name: np.asarray(v) for name, v in f.arrays.items()}
    roots = np.asarray(f.roots, dtype=np.int64)
    d = index.db.shape[1]
    leaf_sz_now = arrays["leaf_entities"].shape[1]
    maxN = max(max((N1 - N0 for _, _, N0, N1, _, _ in slices), default=0), 1)
    maxL = max(max((L1 - L0 for *_, L0, L1 in slices), default=0), 1)

    if shapes is None:
        shapes = ForestShardShapes(
            n_dev=n_dev, kloc=Kloc, cap=cap_now, nodes=maxN, leaves=maxL,
            leaf_sz=leaf_sz_now, max_depth=f.max_depth)
    else:
        over = []
        if shapes.n_dev != n_dev:
            over.append(f"n_dev {n_dev} != {shapes.n_dev}")
        if Kloc > shapes.kloc:
            over.append(f"kloc {Kloc} > {shapes.kloc}")
        if cap_now > shapes.cap:
            over.append(f"cap {cap_now} > {shapes.cap}")
        if maxN > shapes.nodes:
            over.append(f"nodes {maxN} > {shapes.nodes}")
        if maxL > shapes.leaves:
            over.append(f"leaves {maxL} > {shapes.leaves}")
        if leaf_sz_now > shapes.leaf_sz:
            over.append(f"leaf_sz {leaf_sz_now} > {shapes.leaf_sz}")
        if f.max_depth > shapes.max_depth:
            over.append(f"max_depth {f.max_depth} > {shapes.max_depth}")
        if over:
            raise ValueError(
                "forest outgrew the reserved shard shapes ("
                + ", ".join(over)
                + "); rebuild the backend (or raise headroom)")
    Kloc, cap = shapes.kloc, shapes.cap
    padN, padL, leaf_sz = shapes.nodes, shapes.leaves, shapes.leaf_sz
    dead = padN                               # per-shard dead-leaf node id

    out = {
        "proj": np.zeros((n_dev, padN + 1, d), np.float32),
        "dims": np.zeros((n_dev, padN + 1), arrays["dims"].dtype),
        "tau": np.zeros((n_dev, padN + 1), np.float32),
        "children": np.full((n_dev, padN + 1, 2), -1, np.int32),
        "leaf_row": np.full((n_dev, padN + 1), -1, np.int32),
        "leaf_entities": np.full((n_dev, padL, leaf_sz), -1, np.int32),
        "roots": np.full((n_dev, Kloc), dead, np.int32),
        "valid": np.zeros((n_dev, Kloc), bool),
        "cents": np.zeros((n_dev, Kloc, d), np.float32),
        "bucket_ids": np.full((n_dev, Kloc, cap), -1, np.int32),
        "bvecs": np.zeros((n_dev, Kloc, cap, d), np.float32),
    }
    for s, (b0, b1, N0, N1, L0, L1) in enumerate(slices):
        nb, nn, nl = b1 - b0, N1 - N0, L1 - L0
        if nb == 0:
            continue
        ch = arrays["children"][N0:N1].copy()
        ch[ch >= 0] -= N0
        lr = arrays["leaf_row"][N0:N1].copy()
        lr[lr >= 0] -= L0
        out["proj"][s, :nn] = arrays["proj"][N0:N1]
        out["dims"][s, :nn] = arrays["dims"][N0:N1]
        out["tau"][s, :nn] = arrays["tau"][N0:N1]
        out["children"][s, :nn] = ch
        out["leaf_row"][s, :nn] = lr
        out["roots"][s, :nb] = (roots[b0:b1] - N0).astype(np.int32)
        out["valid"][s, :nb] = True
        out["cents"][s, :nb] = index.centroids[b0:b1]
        bl = index.bucket_ids[b0:b1]
        out["bucket_ids"][s, :nb, :cap_now] = bl
        bv = index.db[np.maximum(bl, 0)]
        out["bvecs"][s, :nb, :cap_now] = np.where((bl >= 0)[..., None], bv,
                                                  0.0)
        # global entity id -> local bucket-slot id for this shard's leaves
        # (deleted entities are absent from bucket_ids -> slot -1)
        slot_of = np.full(index.db.shape[0], -1, np.int64)
        rr, cc = np.nonzero(bl >= 0)
        slot_of[bl[rr, cc]] = rr * cap + cc
        le = arrays["leaf_entities"][L0:L1]
        le = np.pad(le, ((0, 0), (0, leaf_sz - le.shape[1])),
                    constant_values=-1).copy()
        m = le >= 0
        le[m] = slot_of[le[m]]
        out["leaf_entities"][s, :nl] = le
    out["max_depth"] = shapes.max_depth
    return out


def _slab_slot_of(index, Kloc: int, cap: int) -> np.ndarray:
    """Global entity id -> slab bucket-slot id (``(b % Kloc) * cap + col``)
    for every placed entity; -1 for deleted/absent.  One vectorized pass,
    shared by the full slab slicer and the delta slicer."""
    rr, cc = np.nonzero(index.bucket_ids >= 0)
    keep = cc < cap          # per-bucket overflow is diagnosed later
    rr, cc = rr[keep], cc[keep]
    slot_of = np.full(index.db.shape[0], -1, np.int64)
    slot_of[index.bucket_ids[rr, cc]] = (rr % Kloc) * cap + cc
    return slot_of


def _bucket_slab_payload(index, shapes: ForestShardShapes, b: int, j: int,
                         arrays: dict, roots: np.ndarray,
                         windows, slot_of: np.ndarray) -> dict:
    """One bucket's fixed-shape slab: every per-bucket array padded to the
    reserved slab sizes, node/leaf offsets rebased to slot ``j``'s
    windows, leaf entity ids remapped to the shard's bucket-slot ids
    (via the precomputed ``slot_of``).  Raises when the bucket outgrew a
    reservation (the same loud contract as the packed slicer)."""
    N0, N1, L0, L1 = windows[b]
    nb, nl = N1 - N0, L1 - L0
    cap, node_slab, leaf_slab = shapes.cap, shapes.node_slab, shapes.leaf_slab
    d = index.db.shape[1]
    over = []
    if nb > node_slab:
        over.append(f"nodes {nb} > slab {node_slab}")
    if nl > leaf_slab:
        over.append(f"leaves {nl} > slab {leaf_slab}")
    bl_full = index.bucket_ids[b]
    count = int((bl_full >= 0).sum())
    if count > cap:
        over.append(f"bucket count {count} > cap {cap}")
    le_w = arrays["leaf_entities"].shape[1]
    if le_w > shapes.leaf_sz:
        over.append(f"leaf_sz {le_w} > {shapes.leaf_sz}")
    if over:
        raise ValueError(
            f"bucket {b} outgrew the reserved slab shapes ("
            + ", ".join(over) + "); rebuild the backend (or raise headroom)")

    proj = np.zeros((node_slab, d), np.float32)
    dims = np.zeros((node_slab,), arrays["dims"].dtype)
    tau = np.zeros((node_slab,), np.float32)
    children = np.full((node_slab, 2), -1, np.int32)
    leaf_row = np.full((node_slab,), -1, np.int32)
    leaf_ents = np.full((leaf_slab, shapes.leaf_sz), -1, np.int32)
    proj[:nb] = arrays["proj"][N0:N1]
    dims[:nb] = arrays["dims"][N0:N1]
    tau[:nb] = arrays["tau"][N0:N1]
    ch = arrays["children"][N0:N1].astype(np.int32, copy=True)
    ch[ch >= 0] += j * node_slab - N0
    children[:nb] = ch
    lr = arrays["leaf_row"][N0:N1].astype(np.int32, copy=True)
    lr[lr >= 0] += j * leaf_slab - L0
    leaf_row[:nb] = lr

    # global entity id -> this shard's bucket-slot id (deleted entities
    # are absent from bucket_ids -> slot -1 via the shared slot_of map)
    bids = np.full((cap,), -1, np.int32)
    w = min(cap, bl_full.shape[0])
    bids[:w] = bl_full[:w]
    le = arrays["leaf_entities"][L0:L1]
    le = np.pad(le, ((0, 0), (0, shapes.leaf_sz - le.shape[1])),
                constant_values=-1).astype(np.int32, copy=True)
    m = le >= 0
    le[m] = slot_of[le[m]]
    leaf_ents[:nl] = le

    bv = index.db[np.maximum(bids, 0)].astype(np.float32)
    bv = np.where((bids >= 0)[:, None], bv, 0.0)
    return {
        "proj": proj, "dims": dims, "tau": tau, "children": children,
        "leaf_row": leaf_row, "leaf_entities": leaf_ents,
        "roots": np.int32(j * node_slab + int(roots[b] - N0)),
        "valid": True,
        "cents": index.centroids[b].astype(np.float32),
        "bucket_ids": bids, "bvecs": bv,
    }


def _shard_forest_slab(index, shapes: ForestShardShapes) -> dict:
    """Slab-layout slicer: same output contract as the packed
    ``shard_forest`` (stacked host arrays + ``max_depth``), but bucket
    ``b`` always lands at slot ``b % kloc`` of shard ``b // kloc`` with
    fixed node/leaf windows — so a mutated bucket later re-ships as a
    standalone slab (:func:`slice_forest_delta`)."""
    f = index.forest
    K = index.bucket_ids.shape[0]
    n_dev, Kloc = shapes.n_dev, shapes.kloc
    if -(-K // n_dev) > Kloc:
        raise ValueError(
            f"forest outgrew the reserved shard shapes (kloc "
            f"{-(-K // n_dev)} > {Kloc}); rebuild the backend")
    if f.max_depth > shapes.max_depth:
        raise ValueError(
            f"forest outgrew the reserved shard shapes (max_depth "
            f"{f.max_depth} > {shapes.max_depth}); rebuild the backend "
            "(or raise headroom)")
    arrays = {name: np.asarray(v) for name, v in f.arrays.items()}
    roots = np.asarray(f.roots, dtype=np.int64)
    windows = _bucket_windows(index)
    d = index.db.shape[1]
    padN, padL, cap = shapes.nodes, shapes.leaves, shapes.cap
    dead = padN                               # per-shard dead-leaf node id
    out = {
        "proj": np.zeros((n_dev, padN + 1, d), np.float32),
        "dims": np.zeros((n_dev, padN + 1), arrays["dims"].dtype),
        "tau": np.zeros((n_dev, padN + 1), np.float32),
        "children": np.full((n_dev, padN + 1, 2), -1, np.int32),
        "leaf_row": np.full((n_dev, padN + 1), -1, np.int32),
        "leaf_entities": np.full((n_dev, padL, shapes.leaf_sz), -1,
                                 np.int32),
        "roots": np.full((n_dev, Kloc), dead, np.int32),
        "valid": np.zeros((n_dev, Kloc), bool),
        "cents": np.zeros((n_dev, Kloc, d), np.float32),
        "bucket_ids": np.full((n_dev, Kloc, cap), -1, np.int32),
        "bvecs": np.zeros((n_dev, Kloc, cap, d), np.float32),
    }
    ns, ls = shapes.node_slab, shapes.leaf_slab
    slot_of = _slab_slot_of(index, Kloc, cap)
    for b in range(K):
        s, j = b // Kloc, b % Kloc
        p = _bucket_slab_payload(index, shapes, b, j, arrays, roots,
                                 windows, slot_of)
        out["proj"][s, j * ns:(j + 1) * ns] = p["proj"]
        out["dims"][s, j * ns:(j + 1) * ns] = p["dims"]
        out["tau"][s, j * ns:(j + 1) * ns] = p["tau"]
        out["children"][s, j * ns:(j + 1) * ns] = p["children"]
        out["leaf_row"][s, j * ns:(j + 1) * ns] = p["leaf_row"]
        out["leaf_entities"][s, j * ls:(j + 1) * ls] = p["leaf_entities"]
        out["roots"][s, j] = p["roots"]
        out["valid"][s, j] = True
        out["cents"][s, j] = p["cents"]
        out["bucket_ids"][s, j] = p["bucket_ids"]
        out["bvecs"][s, j] = p["bvecs"]
    out["max_depth"] = shapes.max_depth
    return out


def slice_forest_delta(index, shapes: ForestShardShapes,
                       dirty_buckets) -> dict:
    """Slice only the dirty buckets into stacked fixed-shape slab
    payloads (slab layout required: ``shapes.node_slab > 0``).

    Returns host arrays keyed like the device tables plus ``shard`` /
    ``slot`` index vectors — the operand set of the backend's jitted
    in-place scatter.  Payload bytes are what a delta republish actually
    ships; compare against the full re-place bytes for the fallback
    decision.
    """
    if shapes.node_slab <= 0:
        raise ValueError("delta slicing requires the slab layout "
                         "(forest_shard_shapes(..., layout='slab'))")
    K = index.bucket_ids.shape[0]
    dirty = np.unique(np.asarray(dirty_buckets, dtype=np.int64))
    if dirty.size and (dirty.min() < 0 or dirty.max() >= K):
        raise ValueError(f"dirty bucket id out of range [0, {K})")
    f = index.forest
    if f.max_depth > shapes.max_depth:
        raise ValueError(
            f"forest outgrew the reserved shard shapes (max_depth "
            f"{f.max_depth} > {shapes.max_depth}); rebuild the backend "
            "(or raise headroom)")
    arrays = {name: np.asarray(v) for name, v in f.arrays.items()}
    roots = np.asarray(f.roots, dtype=np.int64)
    windows = _bucket_windows(index)
    Kloc = shapes.kloc
    slot_of = _slab_slot_of(index, Kloc, shapes.cap)
    rows = [_bucket_slab_payload(index, shapes, int(b), int(b % Kloc),
                                 arrays, roots, windows, slot_of)
            for b in dirty]
    out = {"shard": (dirty // Kloc).astype(np.int32),
           "slot": (dirty % Kloc).astype(np.int32)}
    for name in ("proj", "dims", "tau", "children", "leaf_row",
                 "leaf_entities", "roots", "valid", "cents",
                 "bucket_ids", "bvecs"):
        out[name] = np.stack([p[name] for p in rows]) if rows else \
            np.zeros((0,), np.int32)
    return out


def slice_ivf_delta(index, cap: int, dirty_buckets) -> dict:
    """Dirty-bucket rows of the IVF device tables (centroid, padded slot
    row, gathered bucket-vector tile), ready to scatter at ``rows``."""
    K, cap_now = index.bucket_ids.shape
    if cap < cap_now:
        raise ValueError(
            f"bucket cap grew to {cap_now} > reserved {cap}; rebuild the "
            f"backend (or raise headroom)")
    dirty = np.unique(np.asarray(dirty_buckets, dtype=np.int64))
    if dirty.size and (dirty.min() < 0 or dirty.max() >= K):
        raise ValueError(f"dirty bucket id out of range [0, {K})")
    bids = np.full((dirty.size, cap), -1, np.int32)
    bids[:, :cap_now] = index.bucket_ids[dirty]
    bvecs = index.db[np.maximum(bids, 0)].astype(np.float32)
    bvecs = np.where((bids >= 0)[..., None], bvecs, 0.0)
    return {
        "rows": dirty.astype(np.int32),
        "cents": index.centroids[dirty].astype(np.float32),
        "bucket_ids": bids,
        "bvecs": bvecs,
    }


def make_sharded_forest_fn(mesh, axes: tuple, k: int, nprobe_local: int,
                           beam_width: int, leaf_size: int, max_depth: int,
                           query_axes: tuple = (), *, fused: bool = True):
    """Distributed two-level, tree/QLBT bottom.

    Per chip: score local centroids -> descend the local forest for the
    ``nprobe_local`` best buckets (one batched beam search over the
    shard's node table) -> rerank candidates against the shard's bucket
    vector tile -> global all-gather merge, exactly as the brute/IVF paths.
    """
    from repro.core.tree import tree_search

    _check_disjoint(axes, query_axes)

    def local(cents, valid, roots, bids, bvecs,
              proj, dims, tau, children, leaf_row, leaf_ents, q):
        # every corpus-side array carries a leading length-1 shard dim
        cents, valid, roots = cents[0], valid[0], roots[0]
        bids, bvecs = bids[0], bvecs[0]
        arrays = dict(proj=proj[0], dims=dims[0], tau=tau[0],
                      children=children[0], leaf_row=leaf_row[0],
                      leaf_entities=leaf_ents[0])
        B, dd = q.shape
        np_eff = min(nprobe_local, cents.shape[0])
        d2c = pairwise_l2sq(q, cents)
        d2c = jnp.where(valid[None, :], d2c, jnp.inf)
        _, probe = jax.lax.top_k(-d2c, np_eff)             # (B, np)
        rr = roots[probe].reshape(-1)
        qq = jnp.repeat(q, np_eff, axis=0)                 # (B*np, d)
        vecs_flat = bvecs.reshape(-1, dd)                  # (Kloc*cap, d)
        res = tree_search(
            arrays, vecs_flat, qq, kind="rp", beam_width=beam_width,
            k=beam_width * leaf_size, max_steps=max_depth + 4,
            rerank=False, roots=rr,
        )
        cand = res.ids.reshape(B, -1)                      # local slot ids
        # bucket-slot liveness: a probed slot whose bucket entry is -1
        # holds no servable entity — pad slots, compacted deletes, and
        # (since filters mask bucket_ids the same way) filtered-out rows.
        # For an unfiltered placement every live slot has its entity id
        # in bucket_ids, so this is a no-op there; with a filter mask it
        # is what keeps masked entities from ranking in the rerank.
        flat_bids = bids.reshape(-1)
        cand = jnp.where(
            (cand >= 0) & (flat_bids[jnp.maximum(cand, 0)] >= 0), cand, -1)
        vecs = vecs_flat[jnp.maximum(cand, 0)]
        if fused:
            # rerank distance + top-k in one op (internal clamp/pad to k);
            # slot ids map back to global entity ids afterwards
            ld, slot = kernel_ops.candidate_topk_op(q, vecs, cand, k)
            gids = bids.reshape(-1)[jnp.maximum(slot, 0)]
            li = jnp.where((slot >= 0) & ~jnp.isinf(ld), gids,
                           -1).astype(jnp.int32)
        else:
            d2 = batched_l2sq(vecs, q)
            d2 = jnp.where(cand >= 0, d2, jnp.inf)
            k_eff = min(k, cand.shape[1])
            neg, sel = jax.lax.top_k(-d2, k_eff)
            slot = jnp.take_along_axis(cand, sel, axis=1)
            gids = bids.reshape(-1)[jnp.maximum(slot, 0)]
            gids = jnp.where((slot >= 0) & ~jnp.isinf(-neg), gids, -1)
            ld, li = -neg, gids.astype(jnp.int32)
            if k_eff < k:
                ld = jnp.pad(ld, ((0, 0), (0, k - k_eff)),
                             constant_values=jnp.inf)
                li = jnp.pad(li, ((0, 0), (0, k - k_eff)),
                             constant_values=-1)
        gd = jax.lax.all_gather(ld, axes, tiled=False)
        gi = jax.lax.all_gather(li, axes, tiled=False)
        return _merge_gathered(gd, gi, k)

    qs = _q_spec(query_axes)
    corpus = lambda ndim: P(tuple(axes), *([None] * (ndim - 1)))
    return shard_map(
        local, mesh=mesh,
        in_specs=(corpus(3), corpus(2), corpus(2), corpus(3), corpus(4),
                  corpus(3), corpus(2), corpus(2), corpus(3), corpus(2),
                  corpus(3), qs),
        out_specs=(qs, qs),
        check_vma=False,   # merge all-gathers over the corpus axes only
    )


def _forest_device_arrays(mesh, index, axes, n_dev, shapes=None):
    sh = shard_forest(index, n_dev, shapes=shapes)
    max_depth = sh.pop("max_depth")
    put = lambda x: jax.device_put(
        np.asarray(x),
        NamedSharding(mesh, P(tuple(axes), *([None] * (np.ndim(x) - 1)))),
    )
    return {name: put(v) for name, v in sh.items()}, max_depth


def sharded_forest_search(mesh, index, queries, k=10, nprobe_local=2,
                          beam_width=8, axes=("data", "model"),
                          query_axes=(), fused=True):
    """Host entry: shards a built TwoLevelIndex with a tree/QLBT forest
    bottom level over the mesh and runs the distributed descent."""
    n_dev = _axes_size(mesh, axes)
    dev, max_depth = _forest_device_arrays(mesh, index, axes, n_dev)
    fn = make_sharded_forest_fn(
        mesh, tuple(axes), k, nprobe_local, beam_width,
        index.config.tree_leaf, max_depth, tuple(query_axes), fused=fused,
    )
    q, B = _pad_queries(mesh, queries, query_axes)
    with mesh:
        qs = jax.device_put(q, NamedSharding(mesh, _q_spec(query_axes)))
        d, i = fn(dev["cents"], dev["valid"], dev["roots"],
                  dev["bucket_ids"], dev["bvecs"],
                  dev["proj"], dev["dims"], dev["tau"], dev["children"],
                  dev["leaf_row"], dev["leaf_entities"], qs)
    d, i = jax.device_get((d, i))
    return np.asarray(d)[:B], np.asarray(i)[:B]
