"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

Each function computes the same result as its kernel with no tiling and no
Pallas — used by tests (`tests/test_kernels.py`) for allclose sweeps and by
`ops.py` as the CPU fallback.

Shared contract (matches the kernels): ``k`` is clamped to the candidate
count internally; slots with no live candidate come back as the
``(inf, -1)`` sentinel — callers treat id ``-1`` as "no candidate".  The
distance expansions are *exactly* the ones in ``core.brute``
(``pairwise_l2sq`` / ``batched_l2sq``), so off the chip the sharded scans
compute what ``core.brute`` computes; the conformance suites hold each
Pallas kernel body (interpret mode) to these oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.brute import batched_l2sq, pairwise_l2sq
from repro.kernels.common import popcount32


def _finish(d2, k: int):
    """top_k + sentinel masking + clamp-restoring pad, shared by the
    shared-db oracles (ids are the scan positions)."""
    k_eff = min(k, d2.shape[1])
    neg, ids = jax.lax.top_k(-d2, k_eff)
    d = -neg
    ids = jnp.where(jnp.isinf(d), -1, ids.astype(jnp.int32))
    if k_eff < k:
        d = jnp.pad(d, ((0, 0), (0, k - k_eff)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return d, ids


def _apply_valid(d2, valid):
    if valid is None:
        return d2
    live = jnp.asarray(valid).astype(jnp.int32) != 0
    return jnp.where(live[None, :], d2, jnp.inf)


def l2_topk_ref(queries, db, k: int = 10, *, valid=None):
    q = queries.astype(jnp.float32)
    x = db.astype(jnp.float32)
    d2 = pairwise_l2sq(q, x)
    return _finish(_apply_valid(d2, valid), k)


def l2_topk_int8_ref(queries, db_codes, scales, k: int = 10, *, valid=None):
    """Oracle for the int8-footprint scan: dequantized term-by-term the
    same way the kernel does (scale applied to the reduced terms)."""
    q = queries.astype(jnp.float32)
    xf = db_codes.astype(jnp.float32)
    s = scales.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    xn8 = jnp.sum(xf * xf, axis=1)
    d2 = qn + (s * s * xn8)[None, :] - 2.0 * s[None, :] * (q @ xf.T)
    return _finish(_apply_valid(d2, valid), k)


def candidate_topk_ref(queries, vecs, ids, k: int = 10,
                       *, best_d=None, best_i=None):
    """Oracle for `bucket_topk`: per-query candidate tiles, optional
    carried running best (the IVF probe-chain pattern).

    Uses ``batched_l2sq`` + ``lax.top_k``, the same expansion as
    ``core.brute``; the IVF probe chain through the kernel body matches
    it bitwise (``tests/test_conformance.py``).
    """
    q = queries.astype(jnp.float32)
    v = vecs.astype(jnp.float32)
    ids = ids.astype(jnp.int32)
    d2 = jnp.where(ids >= 0, batched_l2sq(v, q), jnp.inf)
    if best_d is not None:
        cat_d = jnp.concatenate([best_d.astype(jnp.float32), d2], axis=1)
        cat_i = jnp.concatenate([best_i.astype(jnp.int32), ids], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, k)
        d = -neg
        out_i = jnp.take_along_axis(cat_i, sel, axis=1)
    else:
        k_eff = min(k, ids.shape[1])
        neg, sel = jax.lax.top_k(-d2, k_eff)
        d = -neg
        out_i = jnp.take_along_axis(ids, sel, axis=1)
        if k_eff < k:
            d = jnp.pad(d, ((0, 0), (0, k - k_eff)),
                        constant_values=jnp.inf)
            out_i = jnp.pad(out_i, ((0, 0), (0, k - k_eff)),
                            constant_values=-1)
    return d, jnp.where(jnp.isinf(d), -1, out_i)


def pq_adc_topk_ref(lut, codes, k: int = 10, *, valid=None):
    lut = lut.astype(jnp.float32)
    c = codes.astype(jnp.int32)                    # (N, M)
    # scores[b, n] = sum_m lut[b, m, c[n, m]]
    g = jnp.take_along_axis(
        lut, c.T[None, :, :], axis=2
    )                                              # (B, M, N)
    scores = g.sum(axis=1)
    return _finish(_apply_valid(scores, valid), k)


def hamming_topk_ref(qcodes, codes, k: int = 10, *, valid=None):
    x = jnp.bitwise_xor(qcodes[:, None, :], codes[None, :, :])
    ham = popcount32(x).sum(-1).astype(jnp.float32)
    return _finish(_apply_valid(ham, valid), k)


def bm25_dists_ref(q_terms, q_weights, terms, tf_sat):
    """(B, N) BM25 ranking distances (``-score``), reduced in the same
    (term-slot, then doc-slot) order as the kernel's static loop, so the
    kernel body and this oracle sum the same terms in the same order."""
    qt = q_terms.astype(jnp.int32)
    qw = q_weights.astype(jnp.float32)
    t = terms.astype(jnp.int32)
    f = tf_sat.astype(jnp.float32)
    score = jnp.zeros((qt.shape[0], t.shape[0]), jnp.float32)
    for slot in range(qt.shape[1]):
        s = qt[:, slot]                                       # (B,)
        m = (t[None, :, :] == s[:, None, None]) & (
            s[:, None, None] >= 0)                            # (B, N, S)
        hit = jnp.sum(jnp.where(m, f[None, :, :], 0.0), axis=-1)
        score = score + hit * qw[:, slot][:, None]
    return -score


def bm25_topk_ref(q_terms, q_weights, terms, tf_sat, k: int = 10,
                  *, valid=None):
    """Oracle for the fused BM25 scan (dists = -score, ascending)."""
    dist = bm25_dists_ref(q_terms, q_weights, terms, tf_sat)
    return _finish(_apply_valid(dist, valid), k)


def hybrid_topk_ref(queries, db, q_terms, q_weights, terms, tf_sat,
                    alpha, k: int = 10, *, valid=None):
    """Oracle for the fused hybrid scan:
    ``alpha * l2sq - (1 - alpha) * bm25``, ``alpha`` a (1, 1) operand."""
    q = queries.astype(jnp.float32)
    x = db.astype(jnp.float32)
    d2 = pairwise_l2sq(q, x)
    score = -bm25_dists_ref(q_terms, q_weights, terms, tf_sat)
    a = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    dist = a * d2 - (1.0 - a) * score
    return _finish(_apply_valid(dist, valid), k)
