"""Fused L2-distance + streaming top-k Pallas TPU kernel.

The paper's hot loop: brute-force scan of probed buckets / small corpora
(§5.2 found brute the best bottom level at ~100-entity buckets).  On TPU
the scan is an MXU matmul per (query-tile x db-tile) using the expansion
``||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2`` with the running top-k held in
the revisited output block (sequential innermost grid dim).

Grid: (B_tiles, N_tiles), N innermost.  VMEM per step:
  q tile (BQ, D) + x tile (BN, D) + dist tile (BQ, BN) + best (BQ, K)*2
e.g. BQ=256, BN=512, D=128 fp32 ~ (128 + 256 + 512) KiB * 4 -> well under
the ~16 MiB VMEM budget; BN is the tuning knob for arithmetic intensity.

Liveness: every kernel takes a ``valid`` row mask (tombstoned / mutated
shards keep dead rows in place — see ``distributed/sharding.py``); dead
rows score +inf and can never outrank a live candidate.  Result slots
that never saw a live row return the ``(inf, -1)`` sentinel — callers
must treat id ``-1`` as "no candidate" (the `_rerank`-style consumers
mask it uniformly).  ``k`` is clamped to the db row count inside the
wrapper; the requested width is restored by sentinel padding.

``l2_topk_int8_pallas`` is the footprint variant: the db is stored as
int8 codes with one fp32 scale per row (4x less HBM traffic for the
dominant term of this bandwidth-bound scan), accumulated in fp32 on the
MXU via ``preferred_element_type``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    HIGHEST, INF, merge_topk, pad_sentinel, valid_operand,
)

DEFAULT_BQ = 256
DEFAULT_BN = 512


def _mask_tile(d2, v_ref, step, bn: int, n: int):
    """Grid pads (row id >= n) and dead rows (valid == 0) score +inf;
    returns (masked distances, global row ids) for the merge."""
    ids = step * bn + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    live = (ids < n) & (v_ref[...] != 0)
    return jnp.where(live, d2, INF), ids


def _kernel(q_ref, x_ref, v_ref, bd_ref, bi_ref, *, k: int, bn: int, n: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # (BQ, D)
    x = x_ref[...].astype(jnp.float32)            # (BN, D)

    qn = jnp.sum(q * q, axis=1, keepdims=True)    # (BQ, 1)
    xn = jnp.sum(x * x, axis=1)                   # (BN,)
    # MXU: (BQ, D) @ (D, BN)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )
    d2 = qn + xn[None, :] - 2.0 * dots            # (BQ, BN)
    d2, ids = _mask_tile(d2, v_ref, step, bn, n)

    new_d, new_i = merge_topk(bd_ref[...], bi_ref[...], d2, ids, k)
    bd_ref[...] = new_d
    bi_ref[...] = new_i


def _kernel_int8(q_ref, x_ref, s_ref, v_ref, bd_ref, bi_ref,
                 *, k: int, bn: int, n: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # (BQ, D)
    x8 = x_ref[...]                               # (BN, D) int8
    s = s_ref[...][0]                             # (BN,) fp32 row scales

    # int8 codes ride the MXU with fp32 accumulation; the per-row scale
    # is applied to the *reduced* terms, so the cheap operand stays int8
    # all the way through the dominant (D-contraction) traffic
    xf = x8.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)    # (BQ, 1)
    xn8 = jnp.sum(xf * xf, axis=1)                # (BN,) code-space norms
    dots = jax.lax.dot_general(
        q, xf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )                                             # (BQ, BN) code-space
    d2 = qn + (s * s * xn8)[None, :] - 2.0 * s[None, :] * dots
    d2, ids = _mask_tile(d2, v_ref, step, bn, n)

    new_d, new_i = merge_topk(bd_ref[...], bi_ref[...], d2, ids, k)
    bd_ref[...] = new_d
    bi_ref[...] = new_i


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bn", "interpret")
)
def l2_topk_pallas(
    queries: jnp.ndarray,
    db: jnp.ndarray,
    k: int = 10,
    *,
    valid: jnp.ndarray | None = None,
    bq: int = DEFAULT_BQ,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (dists (B, k) ascending fp32, ids (B, k) int32).

    ``valid`` is an optional (N,) liveness mask (bool/int); dead rows are
    unrankable.  Slots beyond the live row count come back as the
    ``(inf, -1)`` sentinel — including the ``k > N`` case, which is
    clamped internally rather than erroring.
    """
    B, D = queries.shape
    N = db.shape[0]
    k_eff = min(k, N)
    bq = min(bq, max(8, B))
    bn = min(bn, max(8, N))
    grid_b = -(-B // bq)
    grid_n = -(-N // bn)
    qp = jnp.pad(queries, ((0, grid_b * bq - B), (0, 0)))
    xp = jnp.pad(db, ((0, grid_n * bn - N), (0, 0)))
    vp = valid_operand(valid, N, grid_n * bn)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k_eff, bn=bn, n=N),
        grid=(grid_b, grid_n),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
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
    )(qp, xp, vp)
    return pad_sentinel(out[0][:B], out[1][:B], k, k_eff)


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bn", "interpret")
)
def l2_topk_int8_pallas(
    queries: jnp.ndarray,
    db_codes: jnp.ndarray,       # (N, D) int8
    scales: jnp.ndarray,         # (N,) fp32 per-row dequant scale
    k: int = 10,
    *,
    valid: jnp.ndarray | None = None,
    bq: int = DEFAULT_BQ,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int8-footprint variant of :func:`l2_topk_pallas`: the db rows are
    int8 codes with a per-row fp32 scale (``row ~= scale * codes``); the
    contraction accumulates in fp32 (``preferred_element_type``).  Same
    clamp / ``valid`` / sentinel contract as the fp32 kernel."""
    B, D = queries.shape
    N = db_codes.shape[0]
    k_eff = min(k, N)
    bq = min(bq, max(8, B))
    bn = min(bn, max(8, N))
    grid_b = -(-B // bq)
    grid_n = -(-N // bn)
    qp = jnp.pad(queries.astype(jnp.float32), ((0, grid_b * bq - B), (0, 0)))
    xp = jnp.pad(db_codes, ((0, grid_n * bn - N), (0, 0)))
    sp = jnp.pad(scales.astype(jnp.float32),
                 (0, grid_n * bn - N))[None, :]
    vp = valid_operand(valid, N, grid_n * bn)

    out = pl.pallas_call(
        functools.partial(_kernel_int8, k=k_eff, bn=bn, n=N),
        grid=(grid_b, grid_n),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
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
    )(qp, xp, sp, vp)
    return pad_sentinel(out[0][:B], out[1][:B], k, k_eff)
