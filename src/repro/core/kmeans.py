"""K-means for two-level partitioning (paper §3.2 step 2).

Lloyd iterations in JAX with chunked assignment (matmul-expanded L2) and
``segment_sum`` centroid updates, plus a mini-batch mode for very large
corpora.  The same assignment kernel handles PQ codebook training
(`core/pq.py`) and bucket routing at build time.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KMeansResult", "kmeans_fit", "kmeans_assign", "pad_to_multiple"]


@dataclasses.dataclass
class KMeansResult:
    centroids: np.ndarray       # (k, d) float32
    assignments: np.ndarray     # (n,) int32
    inertia: float
    n_iter: int


def pad_to_multiple(x: np.ndarray, m: int, axis: int = 0, value=0.0):
    n = x.shape[axis]
    pad = (-n) % m
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=value), n


def _dot_t(x, c):
    """x @ c.T at full f32 precision (the TPU default rounds to bf16), so
    assignments on the chip match the CPU build."""
    return jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("chunk",))
def _assign_chunked(x: jnp.ndarray, c: jnp.ndarray, chunk: int):
    """argmin_j ||x_i - c_j||^2 via scan over query chunks."""
    n, d = x.shape
    c_norm = jnp.sum(c * c, axis=1)                     # (k,)

    def step(_, xi):
        d2 = c_norm[None, :] - 2.0 * _dot_t(xi, c)      # (chunk, k) + const
        a = jnp.argmin(d2, axis=1).astype(jnp.int32)
        best = jnp.min(d2, axis=1) + jnp.sum(xi * xi, axis=1)
        return None, (a, best)

    xs = x.reshape(n // chunk, chunk, d)
    _, (a, best) = jax.lax.scan(step, None, xs)
    return a.reshape(n), best.reshape(n)


@partial(jax.jit, static_argnames=("chunk", "m"))
def _assign_topm_chunked(x: jnp.ndarray, c: jnp.ndarray, m: int, chunk: int):
    n, d = x.shape
    c_norm = jnp.sum(c * c, axis=1)

    def step(_, xi):
        d2 = c_norm[None, :] - 2.0 * _dot_t(xi, c)
        neg, ids = jax.lax.top_k(-d2, m)
        return None, (ids.astype(jnp.int32),
                      -neg + jnp.sum(xi * xi, axis=1, keepdims=True))

    xs = x.reshape(n // chunk, chunk, d)
    _, (ids, d2) = jax.lax.scan(step, None, xs)
    return ids.reshape(n, m), d2.reshape(n, m)


# rows per device call of the host helpers: bounds device memory for any
# corpus (on TPU a (rows, m) int/float result pads m to 128 lanes, so a
# 10M-row top-4 assignment would need ~10 GB of padding alone)
BLOCK_ROWS = 1 << 20


def _blocked(fn, x: np.ndarray, chunk: int, *args):
    """Run ``fn(x_block, *args, chunk)`` over blocks of at most
    ``BLOCK_ROWS`` rows (each padded to a whole number of chunks) and
    concatenate the per-row results on the host."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    chunk = min(chunk, max(1, x.shape[0]))
    rows = max(chunk, BLOCK_ROWS // chunk * chunk)
    outs = []
    for s in range(0, x.shape[0], rows):
        xb, n = pad_to_multiple(x[s:s + rows], chunk)
        outs.append([np.asarray(r[:n])
                     for r in fn(jnp.asarray(xb), *args, chunk)])
    return tuple(np.concatenate(parts) for parts in zip(*outs))


def _assign_topm(x: np.ndarray, centroids: np.ndarray, m: int,
                 chunk: int = 4096):
    """Host helper: m nearest centroids per row (ids, sq-dists)."""
    return _blocked(_assign_topm_chunked, x, chunk, jnp.asarray(centroids),
                    m)


def kmeans_assign(x: np.ndarray, centroids: np.ndarray, chunk: int = 4096):
    """Host helper: nearest-centroid ids for (possibly huge) x."""
    return _blocked(_assign_chunked, x, chunk, jnp.asarray(centroids))


@partial(jax.jit, static_argnames=("k", "chunk"))
def _lloyd_iter(x: jnp.ndarray, c: jnp.ndarray, k: int, chunk: int):
    a, d2 = _assign_chunked(x, c, chunk)
    sums = jax.ops.segment_sum(x, a, num_segments=k)
    cnts = jax.ops.segment_sum(jnp.ones_like(a, jnp.float32), a,
                               num_segments=k)
    new_c = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts, 1)[:, None],
                      c)
    return new_c, a, d2.sum(), cnts


def _init_centroids(rng: np.random.Generator, x: np.ndarray, k: int,
                    init: str) -> np.ndarray:
    n = x.shape[0]
    if init == "random" or k >= n:
        ids = rng.choice(n, size=min(k, n), replace=False)
        c = x[ids]
        if k > n:  # degenerate: duplicate
            c = np.concatenate([c, c[rng.integers(0, n, k - n)]], 0)
        return c.astype(np.float32)
    if init == "kmeans++":  # exact D^2 sampling; fine for k <= ~4096
        ids = [int(rng.integers(0, n))]
        d2 = ((x - x[ids[0]]) ** 2).sum(1)
        for _ in range(k - 1):
            probs = d2 / (d2.sum() + 1e-30)
            nxt = int(rng.choice(n, p=probs))
            ids.append(nxt)
            d2 = np.minimum(d2, ((x - x[nxt]) ** 2).sum(1))
        return x[np.asarray(ids)].astype(np.float32)
    raise ValueError(f"unknown init {init!r}")


def kmeans_fit(
    x: np.ndarray,
    k: int,
    *,
    iters: int = 15,
    chunk: int = 4096,
    seed: int = 0,
    init: str = "random",
    minibatch: int | None = None,
    tol: float = 1e-4,
) -> KMeansResult:
    """Lloyd (or mini-batch) k-means. Deterministic given ``seed``.

    ``minibatch``: if set, each iteration runs Lloyd on a fresh uniform
    sample of that size (Sculley-style), then a final full assignment —
    used for the 2^13..2^15-cluster builds on 1M+ corpora.
    """
    x = np.ascontiguousarray(x, dtype=np.float32)
    n = x.shape[0]
    rng = np.random.default_rng(seed)
    c = _init_centroids(rng, x, k, init)
    chunk = min(chunk, max(1, n))

    prev = np.inf
    it = 0
    for it in range(1, iters + 1):
        if minibatch is not None and minibatch < n:
            sample = x[rng.choice(n, size=minibatch, replace=False)]
        else:
            sample = x
        sp, sn = pad_to_multiple(sample, chunk)
        # padded rows park on centroid of their own (they're zeros); mask by
        # assigning them weight via distance -> they still land somewhere, so
        # instead drop them: run on the largest chunk-multiple prefix.
        m = (sample.shape[0] // chunk) * chunk
        if m == 0:
            m = sample.shape[0]
            sp = sample
            local_chunk = m
        else:
            sp = sample[:m]
            local_chunk = chunk
        new_c, _, inertia, _ = _lloyd_iter(
            jnp.asarray(sp), jnp.asarray(c), k, local_chunk
        )
        new_c = np.asarray(new_c)
        inertia = float(inertia)
        shift = float(np.abs(new_c - c).max())
        c = new_c
        if shift < tol or abs(prev - inertia) < tol * max(prev, 1.0):
            break
        prev = inertia

    a, d2 = kmeans_assign(x, c, chunk=chunk)
    return KMeansResult(centroids=c, assignments=a,
                        inertia=float(d2.sum()), n_iter=it)
