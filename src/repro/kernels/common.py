"""Shared helpers for the ANN Pallas kernels.

All three kernels (`l2_topk`, `pq_adc`, `hamming`) are streaming scans over
database tiles with a running per-query top-k kept in the revisited output
block — the canonical TPU accumulation pattern (sequential innermost grid
dimension revisits the same output tile).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF = float("inf")  # python float: jnp closures may not capture arrays
# f32 dots at full precision: Mosaic's and XLA's TPU default rounds the
# operands to bf16, too coarse for exact-recall L2 on large-norm corpora
HIGHEST = jax.lax.Precision.HIGHEST


def merge_topk(best_d, best_i, tile_d, tile_i, k: int):
    """Merge a (B, T) score tile into the running (B, K) best lists.

    K is static and small (<=32); extraction is K iterative masked mins —
    no sort needed, VPU-friendly, works identically under Pallas interpret
    mode and on the TPU vector unit.

    Ordering is deterministic on the **(distance, id) pair**: equal
    distances break toward the smaller id, matching ``lax.top_k``'s
    lower-index-first rule on an id-ordered scan.  A plain per-round
    ``argmin`` would instead prefer whichever tied candidate entered the
    running list in an earlier tile — an order that depends on the ``bn``
    tiling — so the lexicographic rule is what makes fused-vs-reference
    conformance bitwise rather than merely set-equal.

    The K rounds run as a loop over the concatenated scores carried in
    the loop state, so every round's min and tie test read the same
    stored values.  Unrolled, XLA is free to re-evaluate the (fused)
    distance arithmetic separately for the min and for the ``==`` test;
    the two evaluations can differ in the last bit, no entry then ties
    the minimum, and the id ``int32 max`` would be emitted.  NaN scores
    rank as +inf for the same reason: NaN ties nothing.
    Returns updated (best_d (B,K) ascending, best_i (B,K)).
    """
    cat_d = jnp.concatenate([best_d, tile_d], axis=1)          # (B, K+T)
    cat_i = jnp.concatenate([best_i, tile_i], axis=1)
    cat_d = jnp.where(jnp.isnan(cat_d), INF, cat_d)
    imax = jnp.iinfo(jnp.int32).max
    col = jax.lax.broadcasted_iota(jnp.int32, (cat_d.shape[0], k), 1)

    def round_(r, carry):
        cat_d, out_d, out_i = carry
        md = jnp.min(cat_d, axis=1, keepdims=True)             # (B, 1)
        tie = cat_d == md
        mi = jnp.min(jnp.where(tie, cat_i, imax), axis=1, keepdims=True)
        out_d = jnp.where(col == r, md, out_d)
        out_i = jnp.where(col == r, mi, out_i)
        # retire exactly the selected (distance, id) entry; duplicate
        # (INF, -1) sentinels re-selecting is harmless and intended
        cat_d = jnp.where(tie & (cat_i == mi), INF, cat_d)
        return cat_d, out_d, out_i

    init = (cat_d, jnp.full(col.shape, INF, jnp.float32),
            jnp.full(col.shape, -1, jnp.int32))
    _, out_d, out_i = jax.lax.fori_loop(0, k, round_, init)
    return out_d, out_i


def valid_operand(valid, n: int, n_pad: int) -> jnp.ndarray:
    """Liveness mask as a (1, n_pad) int32 kernel operand.

    Grid-pad rows are dead; ``valid=None`` means all ``n`` rows live.
    Kernels broadcast ``v_ref[...] != 0`` against the (BQ, BN) tile.
    """
    if valid is None:
        v = jnp.ones((n,), jnp.int32)
    else:
        v = jnp.asarray(valid).astype(jnp.int32)
    return jnp.pad(v, (0, n_pad - n))[None, :]


def pad_sentinel(d, i, k: int, k_eff: int):
    """Restore the caller's requested ``k`` after an internal clamp: the
    impossible slots are the documented ``(inf, -1)`` sentinel."""
    if k_eff == k:
        return d, i
    return (jnp.pad(d, ((0, 0), (0, k - k_eff)), constant_values=INF),
            jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1))


def popcount32(x):
    """Branch-free popcount on int32 lanes (no popcnt op on the VPU)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return (x * 0x01010101) >> 24
