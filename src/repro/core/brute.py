"""Exact (brute-force) top-k search.

The paper's best-performing bottom level (§5.2): with ~100-entity buckets a
dense scan beats tree/LSH.  On TPU this is an MXU matmul + streaming top-k —
the `kernels/l2_topk` Pallas kernel implements the fused tile loop; this
module is the jnp implementation used as (a) the oracle, (b) the CPU path,
and (c) the chunked whole-corpus scan for ground-truth generation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["l2_topk_exact", "brute_search", "pairwise_l2sq", "batched_l2sq"]

HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_l2sq(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(B, N) squared L2 via the matmul expansion (MXU-friendly).

    Dots run at ``HIGHEST`` precision: the TPU default rounds f32
    operands to bf16, whose error on large-norm corpora (SIFT's 0..255
    range) exceeds the gaps between neighbour distances."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)         # (B, 1)
    xn = jnp.sum(x * x, axis=-1)                        # (N,)
    return qn + xn[None, :] - 2.0 * jnp.matmul(q, x.T, precision=HIGHEST)


def batched_l2sq(vecs: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """(B, C, d) candidates x (B, d) queries -> (B, C) squared L2.

    The per-query candidate-tile counterpart of ``pairwise_l2sq``; every
    rerank/probe scan shares this one expansion so the numerics cannot
    drift between the single-device and sharded paths."""
    return (
        jnp.sum(vecs * vecs, -1)
        - 2.0 * jnp.einsum("bcd,bd->bc", vecs, q, precision=HIGHEST)
        + jnp.sum(q * q, -1, keepdims=True)
    )


@partial(jax.jit, static_argnames=("k", "chunk"))
def l2_topk_exact(
    queries: jnp.ndarray, db: jnp.ndarray, k: int, chunk: int = 65536
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k by streaming db chunks with a running merge.

    Returns (dists (B,k) ascending, ids (B,k)).  ``db`` rows beyond the
    chunk grid are handled by padding with +inf distance.
    """
    queries = queries.astype(jnp.float32)
    db = db.astype(jnp.float32)
    B = queries.shape[0]
    n = db.shape[0]
    chunk = min(chunk, n)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    dbp = jnp.pad(db, ((0, pad), (0, 0)))

    def step(carry, i):
        best_d, best_i = carry
        start = i * chunk
        xs = jax.lax.dynamic_slice_in_dim(dbp, start, chunk, axis=0)
        d2 = pairwise_l2sq(queries, xs)                  # (B, chunk)
        ids = start + jnp.arange(chunk, dtype=jnp.int32)
        d2 = jnp.where(ids[None, :] < n, d2, jnp.inf)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (B, chunk))], axis=1
        )
        neg, sel = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, sel, axis=1)), None

    best0 = (
        jnp.full((B, k), jnp.inf, jnp.float32),
        jnp.full((B, k), -1, jnp.int32),
    )
    (d, i), _ = jax.lax.scan(step, best0,
                        jnp.arange(n_chunks, dtype=jnp.int32))
    return d, i


def brute_search(
    queries: np.ndarray, db: np.ndarray, k: int, chunk: int = 65536
) -> tuple[np.ndarray, np.ndarray]:
    """Host wrapper returning numpy (dists, ids)."""
    d, i = l2_topk_exact(jnp.asarray(queries), jnp.asarray(db), k,
                         min(chunk, db.shape[0]))
    return np.asarray(d), np.asarray(i)
