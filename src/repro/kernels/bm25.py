"""Fused BM25 lexical scan + hybrid (semantic ⊕ lexical) Pallas kernels.

The hybrid serving mode ranks by
``alpha * ||q - x||^2 - (1 - alpha) * bm25(q, x)`` — semantic L2 fused
with a BM25-ish lexical score in one streaming pass.  Documents carry
fixed-shape postings slabs (``repro.core.lexical``): ``terms`` (N, S)
int32 -1-padded and ``tf_sat`` (N, S) f32, the *saturated* tf factor
precomputed on the host, so the kernel only matches + weights + sums.

Per (query-tile × doc-tile) step the lexical score is a static loop over
the T query term slots (T is small, ~8): each slot broadcasts one term
id against the (BN, S) slab tile, masks, and contracts over S on the
VPU.  The semantic term rides the MXU exactly as in ``l2_topk``.

Tiles are derived from the slab width (:func:`lexical_tiles`): each term
slot's ``(bq, bn, S)`` match temporaries must stay within
``LEX_TEMP_BYTES`` of VMEM (S is padded to 128 lanes), so the query tile
is 8 rows and the doc tile the largest 128-multiple that fits.

``alpha`` is a **(1, 1) operand, not a static argument** — sweeping the
semantic/lexical blend must not mint new executables (the recompile
gate covers the hybrid entry).  Grid, liveness (``valid``), clamp, and
``(inf, -1)`` sentinel contracts match ``l2_topk_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    HIGHEST, INF, merge_topk, pad_sentinel, valid_operand,
)

LEX_BQ = 8
LEX_TEMP_BYTES = 1 << 20


def lexical_tiles(S: int):
    """(bq, bn) for a slab of width S: ``bq * bn * roundup(S, 128) * 4``
    stays within ``LEX_TEMP_BYTES``; bn is a 128-multiple (the ``(1, bn)``
    liveness block's lane rule)."""
    lane = -(-S // 128) * 128 * 4
    return LEX_BQ, max(128, LEX_TEMP_BYTES // (LEX_BQ * lane) // 128 * 128)


def _lexical_tile(qt, qw, terms, tf_sat):
    """(BQ, BN) summed BM25 contributions of a doc tile.

    Static loop over the T query slots: slot t contributes
    ``idf_t * tf_sat[d, s]`` wherever ``terms[d, s] == q_term[b, t]``.
    The (t, then s) reduction order is shared with ``ref.bm25_dists_ref``
    so fused and unfused scores agree bitwise on CPU.
    """
    score = jnp.zeros((qt.shape[0], terms.shape[0]), jnp.float32)
    for t in range(qt.shape[1]):
        slot = qt[:, t]                                       # (BQ,)
        m = (terms[None, :, :] == slot[:, None, None]) & (
            slot[:, None, None] >= 0)                         # (BQ, BN, S)
        hit = jnp.sum(
            jnp.where(m, tf_sat[None, :, :], 0.0), axis=-1)   # (BQ, BN)
        score = score + hit * qw[:, t][:, None]
    return score


def _mask_tile(dist, v_ref, step, bn: int, n: int):
    ids = step * bn + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    live = (ids < n) & (v_ref[...] != 0)
    return jnp.where(live, dist, INF), ids


def _kernel_bm25(qt_ref, qw_ref, t_ref, f_ref, v_ref, bd_ref, bi_ref,
                 *, k: int, bn: int, n: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    score = _lexical_tile(qt_ref[...], qw_ref[...].astype(jnp.float32),
                          t_ref[...], f_ref[...].astype(jnp.float32))
    dist, ids = _mask_tile(-score, v_ref, step, bn, n)
    new_d, new_i = merge_topk(bd_ref[...], bi_ref[...], dist, ids, k)
    bd_ref[...] = new_d
    bi_ref[...] = new_i


def _kernel_hybrid(q_ref, x_ref, qt_ref, qw_ref, t_ref, f_ref, a_ref,
                   v_ref, bd_ref, bi_ref, *, k: int, bn: int, n: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # (BQ, D)
    x = x_ref[...].astype(jnp.float32)            # (BN, D)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    xn = jnp.sum(x * x, axis=1)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )
    d2 = qn + xn[None, :] - 2.0 * dots            # (BQ, BN)
    score = _lexical_tile(qt_ref[...], qw_ref[...].astype(jnp.float32),
                          t_ref[...], f_ref[...].astype(jnp.float32))
    a = a_ref[0, 0]
    dist = a * d2 - (1.0 - a) * score
    dist, ids = _mask_tile(dist, v_ref, step, bn, n)
    new_d, new_i = merge_topk(bd_ref[...], bi_ref[...], dist, ids, k)
    bd_ref[...] = new_d
    bi_ref[...] = new_i


def _grid(bsz, n, S, bq, bn):
    auto_bq, auto_bn = lexical_tiles(S)
    bq = min(bq or auto_bq, max(8, bsz))
    bn = min(bn or auto_bn, max(8, n))
    return bq, bn, -(-bsz // bq), -(-n // bn)


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def bm25_topk_pallas(
    q_terms: jnp.ndarray,        # (B, T) int32, -1 padded
    q_weights: jnp.ndarray,      # (B, T) f32 idf weights
    terms: jnp.ndarray,          # (N, S) int32, -1 padded
    tf_sat: jnp.ndarray,         # (N, S) f32 saturated tf
    k: int = 10,
    *,
    valid: jnp.ndarray | None = None,
    bq: int | None = None,
    bn: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (ranking dists = -bm25 (B, k) ascending, ids (B, k))."""
    B, T = q_terms.shape
    N, S = terms.shape
    k_eff = min(k, N)
    bq, bn, grid_b, grid_n = _grid(B, N, S, bq, bn)
    qtp = jnp.pad(q_terms, ((0, grid_b * bq - B), (0, 0)),
                  constant_values=-1)
    qwp = jnp.pad(q_weights, ((0, grid_b * bq - B), (0, 0)))
    tp = jnp.pad(terms, ((0, grid_n * bn - N), (0, 0)),
                 constant_values=-1)
    fp = jnp.pad(tf_sat, ((0, grid_n * bn - N), (0, 0)))
    vp = valid_operand(valid, N, grid_n * bn)

    out = pl.pallas_call(
        functools.partial(_kernel_bm25, k=k_eff, bn=bn, n=N),
        grid=(grid_b, grid_n),
        in_specs=[
            pl.BlockSpec((bq, T), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, T), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, S), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, S), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid_b * bq, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((grid_b * bq, k_eff), jnp.int32),
        ],
        interpret=interpret,
    )(qtp, qwp, tp, fp, vp)
    return pad_sentinel(out[0][:B], out[1][:B], k, k_eff)


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def hybrid_topk_pallas(
    queries: jnp.ndarray,        # (B, D) f32
    db: jnp.ndarray,             # (N, D) f32
    q_terms: jnp.ndarray,        # (B, T) int32
    q_weights: jnp.ndarray,      # (B, T) f32
    terms: jnp.ndarray,          # (N, S) int32
    tf_sat: jnp.ndarray,         # (N, S) f32
    alpha: jnp.ndarray,          # (1, 1) f32 blend — operand, not static
    k: int = 10,
    *,
    valid: jnp.ndarray | None = None,
    bq: int | None = None,
    bn: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``alpha * l2sq - (1 - alpha) * bm25`` top-k."""
    B, D = queries.shape
    N = db.shape[0]
    T = q_terms.shape[1]
    S = terms.shape[1]
    k_eff = min(k, N)
    bq, bn, grid_b, grid_n = _grid(B, N, S, bq, bn)
    qp = jnp.pad(queries, ((0, grid_b * bq - B), (0, 0)))
    xp = jnp.pad(db, ((0, grid_n * bn - N), (0, 0)))
    qtp = jnp.pad(q_terms, ((0, grid_b * bq - B), (0, 0)),
                  constant_values=-1)
    qwp = jnp.pad(q_weights, ((0, grid_b * bq - B), (0, 0)))
    tp = jnp.pad(terms, ((0, grid_n * bn - N), (0, 0)),
                 constant_values=-1)
    fp = jnp.pad(tf_sat, ((0, grid_n * bn - N), (0, 0)))
    ap = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    vp = valid_operand(valid, N, grid_n * bn)

    out = pl.pallas_call(
        functools.partial(_kernel_hybrid, k=k_eff, bn=bn, n=N),
        grid=(grid_b, grid_n),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, T), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, T), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, S), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, S), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid_b * bq, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((grid_b * bq, k_eff), jnp.int32),
        ],
        interpret=interpret,
    )(qp, xp, qtp, qwp, tp, fp, ap, vp)
    return pad_sentinel(out[0][:B], out[1][:B], k, k_eff)
