"""Sharded search as a ``ServingEngine`` backend.

The one placement path for the builders in :mod:`repro.distributed.
sharding`.  The backend does the expensive work once at construction
(pad, shard, ``device_put``, build and ``jit`` the shard_map callable) and
leaves only query placement + the collective on the per-batch hot path,
so the engine's micro-batches hit a handful of cached jit shapes.

    eng = ServingEngine.sharded(mesh, index, k=10)        # convenience
    eng = ServingEngine(ShardedSearchBackend(mesh, db))   # explicit

Online updates: :meth:`ShardedSearchBackend.apply_updates` re-places a
*mutated* corpus/index (``add_entities`` / ``delete_entities`` /
``rebalance``) into the device-array shapes recorded at construction, so
the jitted search function — and its compile cache — survives the whole
index lifecycle.  ``headroom`` > 1 reserves growth room (more corpus
rows, wider buckets, bigger rebuilt trees); if a mutation outgrows the
reservation, ``apply_updates`` raises and the caller rebuilds the
backend (a cold, re-jitting path — the thing this class exists to avoid
on the common path).  Placement is serialized against in-flight searches
with a lock, so the engine worker thread never sees a half-swapped
argument tuple.

Delta shipping: ``apply_updates(target, delta=manifest)`` (manifest from
``target.pop_delta()``, see :mod:`repro.core.delta`) re-places only what
the manifest names — appended corpus rows for the brute kind, dirty
bucket rows for IVF, dirty bucket *slabs* for the forest kind (whose
device layout reserves a fixed node/leaf slab per bucket when
``delta_updates=True``).  The update is applied **in place on device** by
a jitted fixed-shape scatter (`.at[rows].set(..., mode="drop")`, i.e.
``dynamic_update_slice`` under the hood; buffers are donated off-CPU), so
a maintenance pass that touched a handful of buckets ships a handful of
slabs instead of the corpus.  The backend falls back to a full re-place
— never an error — when the manifest can't prove coverage
(``base_version`` ahead of the backend's placed version), marks itself
``full``, or when the payload exceeds ``delta_max_fraction`` of the full
re-place bytes (past that point one bulk transfer beats many scatters).
Every apply returns a stats dict (``mode``/``bytes``/``full_bytes``/
``reason``) and feeds the cumulative ``republished_bytes`` counters that
``ServingEngine.stats()`` surfaces.
"""
from __future__ import annotations

import threading
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.annotations import guarded_by
from repro.kernels.ops import quantize_rows_int8
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import get_tracer
from repro.distributed.sharding import (
    _axes_size,
    _brute_device_arrays,
    _brute_int8_device_arrays,
    _bucket_width,
    _forest_device_arrays,
    _ivf_device_arrays,
    _lexical_device_arrays,
    _pad_queries,
    _pad_term_queries,
    _q_spec,
    forest_shard_shapes,
    make_sharded_brute_fn,
    make_sharded_forest_fn,
    make_sharded_hybrid_fn,
    make_sharded_ivf_fn,
    make_sharded_lexical_fn,
    slice_forest_delta,
    slice_ivf_delta,
)

__all__ = ["ShardedSearchBackend"]

# device-array order of the forest argument tuple (matches the jitted
# search signature minus the trailing queries)
_FOREST_ARGS = ("cents", "valid", "roots", "bucket_ids", "bvecs",
                "proj", "dims", "tau", "children", "leaf_row",
                "leaf_entities")


def _pow2(n: int) -> int:
    return max(1, 1 << (max(n, 1) - 1).bit_length())


def _pad_rows(a: np.ndarray, u: int, fill=0) -> np.ndarray:
    """Pad the leading dim of ``a`` to ``u`` with ``fill``."""
    if a.shape[0] == u:
        return a
    pad = np.full((u - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad], axis=0)


class ShardedSearchBackend:
    """Callable ``queries (B, d) -> (dists (B, k), ids (B, k))``.

    ``target`` is either a raw ``(N, d)`` corpus (exact sharded scan) or a
    built ``TwoLevelIndex`` (IVF for a brute bottom, forest descent for a
    tree/qlbt bottom).  ``kind="auto"`` picks accordingly.

    ``delta_updates`` (forest kind) lays the forest out in per-bucket
    slabs so dirty buckets can be delta-shipped; it pads every bucket to
    the largest per-bucket tree, trading device memory for republish
    bandwidth.  ``delta_max_fraction`` is the payload-size cutoff past
    which a delta falls back to one bulk re-place.
    """

    def __init__(self, mesh, target, *, kind: str = "auto", k: int = 10,
                 axes=("data", "model"), query_axes=(),
                 nprobe_local: int = 2, beam_width: int = 8,
                 headroom: float = 1.0, alive=None,
                 delta_updates: bool = True,
                 delta_max_fraction: float = 0.5,
                 precision: str = "f32",
                 metadata=None, lexical=None):
        self.mesh = mesh
        self.k = k
        self.axes = tuple(axes)
        self.query_axes = tuple(query_axes)
        self.headroom = headroom
        self.n_dev = _axes_size(mesh, self.axes)
        self.delta_updates = delta_updates
        self.delta_max_fraction = delta_max_fraction
        self.precision = precision
        self.nprobe_local = nprobe_local
        self.beam_width = beam_width
        self._lock = threading.Lock()
        self._delta_fn = None
        self._delta_fn_masked = None     # brute explicit-alive path
        self._lex_delta_fn = None        # postings-slab append scatter
        # filter surface: metadata snapshot (pinned at placement) + the
        # per-FilterSpec compiled mask operands, both lock-guarded; the
        # cache is cleared on every apply so filters observe metadata
        # with the same staleness as the vectors (docs/filtering.md)
        self.metadata_src = metadata
        self.lexical_src = lexical
        self._meta = None
        self._fmask_cache: dict = {}
        self._host_valid: Optional[np.ndarray] = None
        self._host_bids: Optional[np.ndarray] = None
        self._lex_args = None
        self._fn_lex = None
        self._fn_hyb = None
        self._version: Optional[int] = None
        self._n = 0                      # real corpus rows last placed
        self._full_bytes = 0             # host bytes of a full re-place
        self.last_republish: Optional[dict] = None
        # fixed-footprint telemetry: dispatch/kernel/rerank timings plus
        # republish + compile-signature counters (see docs/observability.md)
        self.metrics = MetricsRegistry()
        self._h_kernel = self.metrics.histogram("kernel_ms")
        self._h_rerank = self.metrics.histogram("rerank_ms")
        self._h_first = self.metrics.histogram("first_call_ms",
                                               lo=1e-2, hi=1e7)
        self._c_dispatches = self.metrics.counter("dispatches")
        self._c_sigs = self.metrics.counter("compile_signatures")
        self._c_repub = self.metrics.counter("republished_bytes")
        self._c_repub_full = self.metrics.counter("republish_full_bytes")
        self._c_delta = self.metrics.counter("delta_applies")
        self._c_full = self.metrics.counter("full_applies")
        # abstract query signatures (shape, dtype) already dispatched —
        # the first call per signature is the one that paid trace+compile
        self._seen_sigs: set = set()

        if kind == "auto":
            if isinstance(target, np.ndarray) or not hasattr(
                    target, "bucket_ids"):
                kind = "brute"
            elif getattr(target, "forest", None) is not None:
                kind = "forest"
            else:
                kind = "ivf"
        self.kind = kind

        if precision not in ("f32", "int8"):
            raise ValueError(
                f"precision must be 'f32' or 'int8', got {precision!r}")
        if precision == "int8" and kind != "brute":
            raise ValueError(
                "precision='int8' is only supported for the brute kind")
        if kind == "brute":
            n = int(np.shape(target)[0])
            self._rows = -(-int(np.ceil(n * headroom)) // self.n_dev)
            self._fn = jax.jit(make_sharded_brute_fn(
                mesh, self.axes, k, self._rows, self.query_axes,
                precision=precision))
        elif kind == "ivf":
            self._K, width = (int(s) for s in target.bucket_ids.shape)
            self._cap = _bucket_width(width, headroom)
            # set once at placement: the reserved width, and the pad slots
            # it adds to every bucket beyond the index's own width
            self.metrics.gauge("ivf_bucket_width").set(self._cap)
            self.metrics.gauge("ivf_bucket_pad_slots").set(self._cap - width)
            Kp = -(-self._K // self.n_dev) * self.n_dev
            self._Kp = Kp
            self._fn = jax.jit(make_sharded_ivf_fn(
                mesh, self.axes, k, nprobe_local, Kp // self.n_dev,
                self._K, self.query_axes))
        elif kind == "forest":
            self._shapes = forest_shard_shapes(
                target, self.n_dev, headroom,
                layout="slab" if delta_updates else "packed")
            self._fn = jax.jit(make_sharded_forest_fn(
                mesh, self.axes, k, nprobe_local, beam_width,
                self._shapes.leaf_sz, self._shapes.max_depth,
                self.query_axes))
        else:
            raise ValueError(f"unknown backend kind {kind!r}")
        if self.lexical_src is None:
            self.lexical_src = getattr(target, "lexical", None)
        if self.lexical_src is not None:
            if kind != "brute" or precision != "f32":
                raise ValueError(
                    "lexical slabs (lexical / hybrid modes) require "
                    "kind='brute', precision='f32'")
            self._fn_lex = jax.jit(make_sharded_lexical_fn(
                mesh, self.axes, k, self._rows, self.query_axes))
            self._fn_hyb = jax.jit(make_sharded_hybrid_fn(
                mesh, self.axes, k, self._rows, self.query_axes))
        self._place(target, alive=alive)

    # -- registry-backed compatibility counters ------------------------
    @property
    def republished_bytes(self) -> int:
        """Cumulative bytes shipped by applies."""
        return self._c_repub.value

    @property
    def republish_full_bytes(self) -> int:
        """What full re-places would have cost."""
        return self._c_repub_full.value

    @property
    def n_delta_applies(self) -> int:
        return self._c_delta.value

    @property
    def n_full_applies(self) -> int:
        return self._c_full.value

    # ------------------------------------------------------------------
    def _corpus_spec(self, ndim: int) -> NamedSharding:
        return NamedSharding(
            self.mesh, P(self.axes, *([None] * (ndim - 1))))

    @guarded_by("_lock")
    def _place(self, target, alive=None) -> None:
        """Pad/shard/device_put ``target`` into the recorded shapes."""
        put = lambda x, spec: jax.device_put(
            x, NamedSharding(self.mesh, spec))
        if self.kind == "brute" and self.precision == "int8":
            codes, scales, valid, n = _brute_int8_device_arrays(
                np.asarray(target, np.float32), self.n_dev,
                rows=self._rows, alive=alive)
            self._full_bytes = sum(int(np.asarray(a).nbytes)
                                   for a in (codes, scales, valid))
            self._n = n
            self._host_valid = np.asarray(valid, bool).copy()
            self._args = (put(codes, P(self.axes, None)),
                          put(scales, P(self.axes)),
                          put(valid, P(self.axes)))
        elif self.kind == "brute":
            db_host = np.asarray(
                getattr(target, "db", target), np.float32)
            dbp, valid, n = _brute_device_arrays(
                db_host, self.n_dev, rows=self._rows, alive=alive)
            self._full_bytes = int(np.asarray(dbp).nbytes
                                   + np.asarray(valid).nbytes)
            self._n = n
            self._host_valid = np.asarray(valid, bool).copy()
            self._args = (put(dbp, P(self.axes, None)),
                          put(valid, P(self.axes)))
            if self.lexical_src is not None:
                slabs = self.lexical_src
                if slabs.n_docs != n:
                    raise ValueError(
                        f"lexical slabs hold {slabs.n_docs} rows for a "
                        f"{n}-row corpus; append_docs must track "
                        "add_entities")
                tp, fp, _, _ = _lexical_device_arrays(
                    slabs.terms, slabs.tf_sat, self.n_dev,
                    rows=self._rows, alive=alive)
                self._full_bytes += int(np.asarray(tp).nbytes
                                        + np.asarray(fp).nbytes)
                self._lex_args = (put(tp, P(self.axes, None)),
                                  put(fp, P(self.axes, None)))
        elif self.kind == "ivf":
            if int(target.bucket_ids.shape[0]) != self._K:
                raise ValueError(
                    f"cluster count changed ({target.bucket_ids.shape[0]} "
                    f"!= {self._K}); rebuild the backend")
            cents, bids, bvecs, _ = _ivf_device_arrays(
                target, self.n_dev, cap=self._cap)
            self._full_bytes = sum(int(np.asarray(a).nbytes)
                                   for a in (cents, bids, bvecs))
            self._n = int(target.db.shape[0])
            self._host_bids = np.asarray(bids, np.int32).copy()
            self._args = (
                put(cents, P(self.axes, None)),
                put(bids, P(self.axes, None)),
                put(bvecs, P(self.axes, None, None)),
            )
        else:  # forest
            dev, _ = _forest_device_arrays(
                self.mesh, target, self.axes, self.n_dev,
                shapes=self._shapes)
            self._full_bytes = sum(int(dev[n].nbytes) for n in _FOREST_ARGS)
            self._n = int(target.db.shape[0])
            self._host_bids = np.asarray(dev["bucket_ids"], np.int32).copy()
            self._args = tuple(dev[name] for name in _FOREST_ARGS)
        self._version = getattr(target, "mutation_version", None)
        self._refresh_meta(target)

    @guarded_by("_lock")
    def _refresh_meta(self, target) -> None:
        """Pin the metadata the *next* filtered queries will see and drop
        every compiled mask — applies move the staleness window for
        filters and vectors together (docs/filtering.md)."""
        meta = (self.metadata_src if self.metadata_src is not None
                else getattr(target, "metadata", None))
        self._meta = meta.snapshot() if meta is not None else None
        self._fmask_cache.clear()

    @guarded_by("_lock")
    def _filter_operand(self, filter_spec):
        """Compile a ``FilterSpec`` to this kind's mask operand (cached
        per spec digest until the next apply).

        brute/lexical/hybrid: the entity mask ANDed into the placed
        ``valid`` row operand.  ivf/forest: filtered entities' slots in
        ``bucket_ids`` masked to -1 — the scan's existing ``id >= 0``
        discipline then keeps them from ranking.  Same shapes and dtypes
        as the unfiltered operands, so the jitted search signature (and
        its compile cache) is untouched — the recompile gate's
        ``filtered-sharded-search`` entry holds this.
        """
        key = filter_spec.key()
        hit = self._fmask_cache.get(key)
        if hit is not None:
            return hit
        put = lambda x, spec: jax.device_put(
            x, NamedSharding(self.mesh, spec))
        if self.kind == "brute":
            emask = filter_spec.mask(self._meta, self._host_valid.shape[0])
            dev = put(self._host_valid & emask, P(self.axes))
        elif self.kind == "ivf":
            emask = filter_spec.mask(self._meta, max(self._n, 1))
            b = self._host_bids
            live = (b >= 0) & emask[np.minimum(np.maximum(b, 0),
                                               emask.shape[0] - 1)]
            dev = put(np.where(live, b, -1).astype(np.int32),
                      P(self.axes, None))
        else:  # forest
            emask = filter_spec.mask(self._meta, max(self._n, 1))
            b = self._host_bids
            live = (b >= 0) & emask[np.minimum(np.maximum(b, 0),
                                               emask.shape[0] - 1)]
            dev = put(np.where(live, b, -1).astype(np.int32),
                      P(self.axes, None, None))
        if len(self._fmask_cache) >= 64:
            self._fmask_cache.clear()
        self._fmask_cache[key] = dev
        return dev

    # ------------------------------------------------------------------
    # delta apply: jitted fixed-shape in-place scatters
    # ------------------------------------------------------------------
    def _make_delta_fn(self):
        """Build the jitted in-place scatter for this backend's kind.

        Fixed shapes: the payload's leading (update-count) dim is padded
        to a power of two with out-of-bounds indices, which
        ``mode="drop"`` discards — so the kernel compiles once per pow2
        bucket, never per mutation.  Buffers are donated off-CPU so the
        update really is in place; the CPU backend doesn't support
        donation, so there we let XLA copy.
        """
        donate_ok = jax.default_backend() != "cpu"
        if self.kind == "brute" and self.precision == "int8":
            specs = (self._corpus_spec(2), self._corpus_spec(1),
                     self._corpus_spec(1))

            @partial(jax.jit,
                     donate_argnums=(0, 1, 2) if donate_ok else (),
                     out_shardings=specs)
            def fn(codes, scales, valid, rows, vals8, vscales, tomb):
                # same cumulative-liveness contract as the f32 scatter,
                # over the quantized (codes, scales) pair
                codes = codes.at[rows].set(vals8, mode="drop")
                scales = scales.at[rows].set(vscales, mode="drop")
                valid = valid.at[rows].set(True, mode="drop")
                valid = valid.at[tomb].set(False, mode="drop")
                return codes, scales, valid

            return fn
        if self.kind == "brute":
            specs = (self._corpus_spec(2), self._corpus_spec(1))

            @partial(jax.jit, donate_argnums=(0, 1) if donate_ok else (),
                     out_shardings=specs)
            def fn(db, valid, rows, vals, tomb):
                # liveness is cumulative ON DEVICE: appended rows flip
                # alive, tombstones flip dead, everything else keeps the
                # bits earlier windows left — a tombstone-only manifest
                # ships two index vectors, not the whole mask
                db = db.at[rows].set(vals, mode="drop")
                valid = valid.at[rows].set(True, mode="drop")
                valid = valid.at[tomb].set(False, mode="drop")
                return db, valid

            return fn
        if self.kind == "ivf":
            specs = tuple(self._corpus_spec(nd) for nd in (2, 2, 3))

            @partial(jax.jit,
                     donate_argnums=(0, 1, 2) if donate_ok else (),
                     out_shardings=specs)
            def fn(cents, bids, bvecs, rows, uc, ub, uv):
                cents = cents.at[rows].set(uc, mode="drop")
                bids = bids.at[rows].set(ub, mode="drop")
                bvecs = bvecs.at[rows].set(uv, mode="drop")
                return cents, bids, bvecs

            return fn
        # forest: scatter whole per-bucket slabs into the 11 tables
        ns, ls = self._shapes.node_slab, self._shapes.leaf_slab
        ndims = (3, 2, 2, 3, 4, 3, 2, 2, 3, 2, 3)   # _FOREST_ARGS dims
        specs = tuple(self._corpus_spec(nd) for nd in ndims)

        @partial(jax.jit,
                 donate_argnums=tuple(range(11)) if donate_ok else (),
                 out_shardings=specs)
        def fn(cents, valid, roots, bids, bvecs, proj, dims, tau,
               children, leaf_row, leaf_ents, shard, slot,
               u_cents, u_valid, u_roots, u_bids, u_bvecs, u_proj,
               u_dims, u_tau, u_children, u_leaf_row, u_leaf_ents):
            sh1 = shard[:, None]
            nrow = slot[:, None] * ns + jnp.arange(ns, dtype=jnp.int32)[None, :]
            lrow = slot[:, None] * ls + jnp.arange(ls, dtype=jnp.int32)[None, :]
            cents = cents.at[shard, slot].set(u_cents, mode="drop")
            valid = valid.at[shard, slot].set(u_valid, mode="drop")
            roots = roots.at[shard, slot].set(u_roots, mode="drop")
            bids = bids.at[shard, slot].set(u_bids, mode="drop")
            bvecs = bvecs.at[shard, slot].set(u_bvecs, mode="drop")
            proj = proj.at[sh1, nrow].set(u_proj, mode="drop")
            dims = dims.at[sh1, nrow].set(u_dims, mode="drop")
            tau = tau.at[sh1, nrow].set(u_tau, mode="drop")
            children = children.at[sh1, nrow].set(u_children, mode="drop")
            leaf_row = leaf_row.at[sh1, nrow].set(u_leaf_row, mode="drop")
            leaf_ents = leaf_ents.at[sh1, lrow].set(u_leaf_ents,
                                                    mode="drop")
            return (cents, valid, roots, bids, bvecs, proj, dims, tau,
                    children, leaf_row, leaf_ents)

        return fn

    def _make_masked_delta_fn(self):
        """Brute-kind scatter for the explicit-``alive`` path: the caller
        ships the complete liveness truth as a mask, so only the corpus
        rows are scattered and the mask is re-placed wholesale."""
        donate_ok = jax.default_backend() != "cpu"
        if self.kind == "brute" and self.precision == "int8":
            specs = (self._corpus_spec(2), self._corpus_spec(1))

            @partial(jax.jit, donate_argnums=(0, 1) if donate_ok else (),
                     out_shardings=specs)
            def fn8(codes, scales, rows, vals8, vscales):
                return (codes.at[rows].set(vals8, mode="drop"),
                        scales.at[rows].set(vscales, mode="drop"))

            return fn8

        @partial(jax.jit, donate_argnums=(0,) if donate_ok else (),
                 out_shardings=self._corpus_spec(2))
        def fn(db, rows, vals):
            return db.at[rows].set(vals, mode="drop")

        return fn

    def _make_lex_delta_fn(self):
        """Postings-slab counterpart of the brute row scatter: appended
        docs land their term/tf slab rows at the same row ids as their
        vectors (liveness rides the shared ``valid`` mask)."""
        donate_ok = jax.default_backend() != "cpu"
        specs = (self._corpus_spec(2), self._corpus_spec(2))

        @partial(jax.jit, donate_argnums=(0, 1) if donate_ok else (),
                 out_shardings=specs)
        def fn(terms, tf, rows, u_terms, u_tf):
            return (terms.at[rows].set(u_terms, mode="drop"),
                    tf.at[rows].set(u_tf, mode="drop"))

        return fn

    def _bucket_payload_bytes(self) -> int:
        """Exact per-dirty-bucket payload size — computable up front
        because every slab/row shape is fixed, so an over-threshold
        manifest is rejected *before* paying the host-side slicing."""
        if self.kind == "ivf":
            d = int(np.asarray(self._args[0]).shape[1])
            return 4 * (d + self._cap + self._cap * d + 1)
        sh = self._shapes
        d = int(np.asarray(self._args[0]).shape[2])
        ns, ls = sh.node_slab, sh.leaf_slab
        return (4 * (ns * d + ns + ns + ns * 2 + ns      # node tables
                     + ls * sh.leaf_sz                   # leaf slab
                     + 1 + d + sh.cap + sh.cap * d       # bucket row
                     + 2)                                # shard/slot
                + 1)                                     # valid flag

    def _delta_payload(self, target, alive, delta):
        """Host-side payload for the manifest, or (None, reason) when the
        delta path can't cover this update."""
        if self.kind == "brute":
            if delta.dirty_buckets.size:
                return None, "bucket-delta-on-flat-corpus"
            if delta.base_n > self._n:
                return None, "version"
            if (self._version is not None
                    and delta.base_version > self._version):
                # a raw-corpus backend has no index version at
                # construction, but once a manifest chain starts a gap
                # in it means missed tombstones — full re-place
                return None, "version"
            db = np.asarray(getattr(target, "db", target), np.float32)
            n = db.shape[0]
            if n > self._rows * self.n_dev:
                return None, "outgrew"        # full place raises loudly
            rows_tot = self._rows * self.n_dev
            new = np.arange(delta.base_n, n, dtype=np.int32)
            vals = db[delta.base_n:n]
            u = _pow2(new.size)
            pay = {"rows": _pad_rows(new, u, fill=rows_tot), "n": n}
            if self.precision == "int8":
                vals8, vscales = quantize_rows_int8(vals)
                pay["vals8"] = _pad_rows(vals8, u)
                pay["vscales"] = _pad_rows(vscales, u, fill=1.0)
                vals_bytes = int(vals8.nbytes + vscales.nbytes)
            else:
                pay["vals"] = _pad_rows(vals, u)
                vals_bytes = int(vals.nbytes)
            if self._lex_args is not None:
                slabs = self.lexical_src
                if slabs is None or slabs.n_docs != n:
                    return None, "lexical-misaligned"
                pay["lex_terms"] = _pad_rows(
                    np.asarray(slabs.terms[delta.base_n:n], np.int32),
                    u, fill=-1)
                pay["lex_tf"] = _pad_rows(
                    np.asarray(slabs.tf_sat[delta.base_n:n], np.float32), u)
                vals_bytes += int(pay["lex_terms"].nbytes
                                  + pay["lex_tf"].nbytes)
            if alive is not None:
                # caller supplied the complete liveness truth: ship the
                # whole mask (it IS the payload — nothing to delta)
                valid = np.arange(rows_tot) < n
                valid[:n] &= np.asarray(alive, bool)
                if delta.tombstones.size:
                    valid[delta.tombstones] = False
                pay["valid"] = valid
                pay["bytes"] = int(vals_bytes + new.nbytes + valid.nbytes)
            else:
                # tombstone-only (and append) windows ship two index
                # vectors; the device mask keeps the bits from earlier
                # windows, so liveness stays cumulative without ever
                # pulling the mask back to host
                tomb = np.asarray(delta.tombstones, np.int32)
                pay["tomb"] = _pad_rows(tomb, _pow2(tomb.size),
                                        fill=rows_tot)
                pay["bytes"] = int(vals_bytes + new.nbytes + tomb.nbytes)
            return pay, None
        if self._version is None or delta.base_version > self._version:
            return None, "version"
        if self.kind == "ivf":
            if int(target.bucket_ids.shape[0]) != self._K:
                return None, "outgrew"
            pay = slice_ivf_delta(target, self._cap, delta.dirty_buckets)
            pay["bytes"] = sum(int(v.nbytes) for v in pay.values())
            pay["n"] = int(target.db.shape[0])
            u = _pow2(pay["rows"].shape[0])
            pay["rows"] = _pad_rows(pay["rows"], u, fill=self._Kp)
            for name in ("cents", "bucket_ids", "bvecs"):
                pay[name] = _pad_rows(pay[name], u)
            return pay, None
        # forest
        if not self.delta_updates:
            return None, "packed-layout"
        pay = slice_forest_delta(target, self._shapes, delta.dirty_buckets)
        pay["bytes"] = sum(int(np.asarray(v).nbytes) for v in pay.values())
        pay["n"] = int(target.db.shape[0])
        u = _pow2(pay["shard"].shape[0])
        pay["shard"] = _pad_rows(pay["shard"], u, fill=self.n_dev)  # OOB
        pay["slot"] = _pad_rows(pay["slot"], u)
        for name in _FOREST_ARGS:
            pay[name] = _pad_rows(np.asarray(pay[name]), u)
        return pay, None

    @guarded_by("_lock")
    def _apply_lex_delta(self, pay) -> None:
        """Scatter appended postings-slab rows next to their vectors."""
        if self._lex_args is None or "lex_terms" not in pay:
            return
        if self._lex_delta_fn is None:
            self._lex_delta_fn = self._make_lex_delta_fn()
        self._lex_args = self._lex_delta_fn(
            self._lex_args[0], self._lex_args[1], pay["rows"],
            pay["lex_terms"], pay["lex_tf"])

    @guarded_by("_lock")
    def _apply_delta(self, pay) -> None:
        if self.kind == "brute" and "valid" in pay:
            if self._delta_fn_masked is None:
                self._delta_fn_masked = self._make_masked_delta_fn()
            valid = jax.device_put(
                pay["valid"], NamedSharding(self.mesh, P(self.axes)))
            if self.precision == "int8":
                codes, scales = self._delta_fn_masked(
                    self._args[0], self._args[1], pay["rows"],
                    pay["vals8"], pay["vscales"])
                self._args = (codes, scales, valid)
            else:
                db = self._delta_fn_masked(
                    self._args[0], pay["rows"], pay["vals"])
                self._args = (db, valid)
                self._apply_lex_delta(pay)
            self._host_valid = np.asarray(pay["valid"], bool).copy()
            self._n = pay["n"]
            return
        if self._delta_fn is None:
            self._delta_fn = self._make_delta_fn()
        if self.kind == "brute" and self.precision == "int8":
            self._args = self._delta_fn(
                self._args[0], self._args[1], self._args[2], pay["rows"],
                pay["vals8"], pay["vscales"], pay["tomb"])
            self._mirror_brute_liveness(pay)
        elif self.kind == "brute":
            self._args = self._delta_fn(
                self._args[0], self._args[1], pay["rows"], pay["vals"],
                pay["tomb"])
            self._apply_lex_delta(pay)
            self._mirror_brute_liveness(pay)
        elif self.kind == "ivf":
            self._args = self._delta_fn(
                *self._args, pay["rows"], pay["cents"],
                pay["bucket_ids"], pay["bvecs"])
            rows = np.asarray(pay["rows"])
            keep = rows < self._host_bids.shape[0]
            self._host_bids[rows[keep]] = np.asarray(
                pay["bucket_ids"])[keep]
        else:
            self._args = self._delta_fn(
                *self._args, pay["shard"], pay["slot"],
                *(pay[name] for name in _FOREST_ARGS))
            sh = np.asarray(pay["shard"])
            sl = np.asarray(pay["slot"])
            keep = sh < self._host_bids.shape[0]
            self._host_bids[sh[keep], sl[keep]] = np.asarray(
                pay["bucket_ids"])[keep]
        self._n = pay["n"]

    @guarded_by("_lock")
    def _mirror_brute_liveness(self, pay) -> None:
        """Replay the device liveness flips on the host mirror the filter
        compiler reads (appends flip alive, tombstones flip dead)."""
        rt = self._host_valid.shape[0]
        rows = np.asarray(pay["rows"])
        self._host_valid[rows[rows < rt]] = True
        tomb = np.asarray(pay["tomb"])
        self._host_valid[tomb[tomb < rt]] = False

    # ------------------------------------------------------------------
    def apply_updates(self, target, alive=None, delta=None) -> dict:
        """Serve a mutated corpus/index through the already-jitted search.

        With ``delta`` (a :class:`repro.core.delta.DeltaManifest`, e.g.
        from ``target.pop_delta()``): scatter only the manifest's dirty
        slices into the live device arrays — no full corpus transfer, no
        re-jit — falling back to a full re-place whenever the manifest
        cannot prove coverage or the payload is no cheaper than bulk.
        Without ``delta``: re-pad and re-place everything into the shapes
        recorded at construction.  Either way, raises ``ValueError`` when
        the mutation outgrew the reservation (rebuild the backend with
        more ``headroom``), the jitted search callable is untouched, and
        queries issued after this call hit the existing compile cache.
        ``alive`` (brute kind only) marks tombstoned corpus rows.

        Returns ``{"mode", "bytes", "full_bytes", "reason"}`` — ``mode``
        is ``"delta"``, ``"full"``, or ``"noop"``; ``bytes`` is what was
        actually shipped; ``full_bytes`` is what a full re-place ships.
        """
        with get_tracer().span("republish.place", kind=self.kind) as sp:
            with self._lock:
                stats = self._apply_locked(target, alive, delta)
                self.last_republish = stats
            # counters are internally locked — concurrent maintenance
            # passes can't lose increments even outside the backend lock
            self._c_repub.inc(stats["bytes"])
            self._c_repub_full.inc(stats["full_bytes"])
            if stats["mode"] == "delta":
                self._c_delta.inc()
            elif stats["mode"] == "full":
                self._c_full.inc()
            sp.set(mode=stats["mode"], bytes=stats["bytes"])
        return stats

    @guarded_by("_lock")
    def _apply_locked(self, target, alive, delta) -> dict:
        reason = None
        if delta is None:
            reason = "no-manifest"
        elif delta.full:
            reason = "manifest-full"
        else:
            covered = (self._version is not None
                       and delta.base_version <= self._version)
            if delta.empty and (covered or self.kind == "brute"):
                self._version = delta.version
                self._refresh_meta(target)
                return {"mode": "noop", "bytes": 0,
                        "full_bytes": self._full_bytes, "reason": None}
            if (self.kind in ("ivf", "forest") and self.delta_updates
                    and delta.dirty_buckets.size * self._bucket_payload_bytes()
                    > self.delta_max_fraction * self._full_bytes):
                # fixed shapes make the payload size exact up front —
                # don't pay the slicing for a delta that can't win
                reason = "threshold"
            else:
                pay, reason = self._delta_payload(target, alive, delta)
            if reason is None:
                if pay["bytes"] > self.delta_max_fraction * self._full_bytes:
                    reason = "threshold"
                else:
                    self._apply_delta(pay)
                    self._version = delta.version
                    self._refresh_meta(target)
                    return {"mode": "delta", "bytes": pay["bytes"],
                            "full_bytes": self._full_bytes, "reason": None}
        self._place(target, alive=alive)
        return {"mode": "full", "bytes": self._full_bytes,
                "full_bytes": self._full_bytes, "reason": reason}

    def jit_cache_size(self) -> int:
        """Compiled-variant count of the underlying search (test hook) —
        summed over the semantic/lexical/hybrid callables."""
        total = 0
        for fn in (self._fn, self._fn_lex, self._fn_hyb):
            if fn is None:
                continue
            try:
                total += int(fn._cache_size())
            except AttributeError:      # older jax: no introspection
                return -1
        return total

    def compiled_text(self, batch: int) -> str:
        """Compiled program text of the semantic search at ``batch``
        queries — where a chip check looks for the Pallas kernel
        (``tpu_custom_call``) rather than the jnp reference."""
        with self._lock:
            args = self._args
        q = jax.ShapeDtypeStruct(
            (batch, int(args[0].shape[-1])), jnp.float32,
            sharding=NamedSharding(self.mesh, _q_spec(self.query_axes)))
        return self._fn.lower(*args, q).compile().as_text()

    def __call__(self, queries, *, filter_spec=None, mode: str = "semantic",
                 alpha: float = 0.5, q_terms=None, q_weights=None):
        """Search.  ``filter_spec`` (a :class:`repro.core.metadata.
        FilterSpec`) restricts results to matching entities; ``mode``
        selects ``"semantic"`` (dense scan), ``"lexical"`` (BM25 over the
        postings slabs), or ``"hybrid"`` (``alpha * l2sq - (1 - alpha) *
        bm25``).  Non-semantic modes need the backend built with lexical
        slabs and per-query ``q_terms``/``q_weights`` operands (see
        :func:`repro.core.lexical.query_operands`).  Filters and alpha are
        data, not shapes — no mode/filter combination mints a new jit
        signature beyond the three per-mode callables.
        """
        tracer = get_tracer()
        if filter_spec is not None and filter_spec.empty:
            filter_spec = None
        if mode not in ("semantic", "lexical", "hybrid"):
            raise ValueError(
                f"mode must be 'semantic', 'lexical', or 'hybrid', "
                f"got {mode!r}")
        if mode != "semantic":
            if self._fn_lex is None:
                raise ValueError(
                    f"mode={mode!r} requires a backend built with lexical "
                    "slabs (kind='brute', lexical=...)")
            if q_terms is None or q_weights is None:
                raise ValueError(
                    f"mode={mode!r} requires q_terms/q_weights (see "
                    "repro.core.lexical.query_operands)")
            qt, qw, B = _pad_term_queries(
                self.mesh, q_terms, q_weights, self.query_axes)
        if mode == "lexical":
            sig = (mode, tuple(qt.shape), str(qt.dtype))
            b_disp = int(qt.shape[0])
        else:
            q, B = _pad_queries(self.mesh, queries, self.query_axes)
            sig = (mode, tuple(q.shape), str(q.dtype))
            b_disp = int(q.shape[0])
        t0 = time.perf_counter()
        # kernel: queue + device execution of the jitted shard_map scan,
        # in two parts: backend.launch (lock wait, query placement and
        # the enqueue, up to the jitted call's return) and backend.wait
        # (block_until_ready).  The wait runs OUTSIDE the lock (same
        # concurrency as before, where device_get did the blocking) so the
        # span measures real device time, not async dispatch.
        with tracer.span("kernel", kind=self.kind, b=b_disp):
            with tracer.span("backend.launch"):
                with self._lock, self.mesh:
                    first = sig not in self._seen_sigs
                    if first:
                        self._seen_sigs.add(sig)
                    qspec = NamedSharding(self.mesh, _q_spec(self.query_axes))
                    args = self._args
                    if filter_spec is not None:
                        fdev = self._filter_operand(filter_spec)
                        if self.kind == "brute":
                            if self.precision == "int8":
                                args = (args[0], args[1], fdev)
                            else:
                                args = (args[0], fdev)
                        elif self.kind == "ivf":
                            args = (args[0], fdev, args[2])
                        else:  # forest: bucket_ids is _FOREST_ARGS[3]
                            args = args[:3] + (fdev,) + args[4:]
                    if mode == "semantic":
                        qs = jax.device_put(q, qspec)
                        d, i = self._fn(*args, qs)
                    elif mode == "lexical":
                        # args[-1] is the (possibly filtered) valid operand
                        qts = jax.device_put(qt, qspec)
                        qws = jax.device_put(qw, qspec)
                        d, i = self._fn_lex(
                            self._lex_args[0], self._lex_args[1], args[1],
                            qts, qws)
                    else:  # hybrid
                        qs = jax.device_put(q, qspec)
                        qts = jax.device_put(qt, qspec)
                        qws = jax.device_put(qw, qspec)
                        a_dev = jax.device_put(
                            np.full((1, 1), float(alpha), np.float32),
                            NamedSharding(self.mesh, P(None, None)))
                        d, i = self._fn_hyb(
                            args[0], self._lex_args[0], self._lex_args[1],
                            args[1], qs, qts, qws, a_dev)
            with tracer.span("backend.wait"):
                jax.block_until_ready((d, i))
        t1 = time.perf_counter()
        # rerank: pull the per-shard top-k merge result back to host and
        # trim query padding — the host half of candidate re-scoring
        with tracer.span("rerank", kind=self.kind):
            d, i = jax.device_get((d, i))
            out = np.asarray(d)[:B], np.asarray(i)[:B]
        t2 = time.perf_counter()
        self._c_dispatches.inc()
        self._h_kernel.observe((t1 - t0) * 1e3)
        self._h_rerank.observe((t2 - t1) * 1e3)
        if first:
            # first dispatch of this abstract signature paid the
            # trace+compile; record it with the signature that triggered it
            self._c_sigs.inc()
            self._h_first.observe((t1 - t0) * 1e3)
            tracer.instant("compile-signature", kind=self.kind,
                           shape=str(list(sig[0])), dtype=sig[1],
                           ms=round((t1 - t0) * 1e3, 3))
        return out
