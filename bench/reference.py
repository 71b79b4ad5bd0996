"""The plain reference and the comparison that decides ``correct``.

The reference is exact nearest-neighbour search over the seeded corpus in
float64 numpy (a copy of the program's oracle, ``exact_topk``).  It takes
the corpus and the queries and nothing the program built: an approximate
index's own buckets and trees are the program's tables, so the index's
approximation is judged against the exact answer by the share of misses,
and every distance the program returned is judged exactly.

Numbers compared, each against a limit of its configuration:

* ``lost``      requests due in the window that never got an answer
                (timed out or raised); a shed request is refused, not lost;
* ``malformed`` answers with a non-finite distance, an id out of range, a
                repeated id, or distances out of ascending order;
* ``dist_err``  the widest gap between a returned distance and the float64
                squared L2 distance of the query to the returned row, over
                ``|q|^2 + |x|^2`` (the scale float32 rounding works at);
* ``top1_miss`` share of the sampled requests whose first id is not the
                exact nearest neighbour;
* ``recall_miss`` 1 - recall@k of the sampled requests against the exact
                top-k;
* ``rest_miss`` share of the sampled answers' ranks 2..k that lie outside
                the exact top-``rest_depth`` (the configuration's, deeper
                than k): the rows an index returns after the nearest one
                must still be near, whatever found the first.
"""
from __future__ import annotations

import numpy as np


def exact_topk(db: np.ndarray, queries: np.ndarray, k: int,
               chunk: int = 1 << 16):
    """Exact top-k (ids, squared distances) in float64 numpy."""
    q = np.asarray(queries, np.float64)
    qn = np.sum(q * q, axis=1, keepdims=True)
    best_d = np.full((q.shape[0], k), np.inf)
    best_i = np.full((q.shape[0], k), -1, np.int64)
    for s in range(0, db.shape[0], chunk):
        x = np.asarray(db[s:s + chunk], np.float64)
        d2 = qn - 2.0 * (q @ x.T) + np.sum(x * x, axis=1)[None, :]
        kk = min(k, d2.shape[1])
        part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
        cat_d = np.concatenate(
            [best_d, np.take_along_axis(d2, part, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, part + s], axis=1)
        order = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cat_d, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
    return best_i, best_d


def answer_checks(db: np.ndarray, queries: np.ndarray, dists: np.ndarray,
                  ids: np.ndarray, block: int = 4096) -> tuple[int, float]:
    """(malformed answers, widest relative distance gap) over every
    answer; ``queries[j]`` is the query answer ``j`` was asked."""
    n = db.shape[0]
    bad = 0
    err = 0.0
    for s in range(0, ids.shape[0], block):
        i = np.asarray(ids[s:s + block], np.int64)
        d = np.asarray(dists[s:s + block], np.float64)
        q = np.asarray(queries[s:s + block], np.float64)
        in_range = ((i >= 0) & (i < n)).all(axis=1)
        srt = np.sort(i, axis=1)
        unique = (srt[:, 1:] != srt[:, :-1]).all(axis=1)
        ordered = (np.diff(d, axis=1) >= 0).all(axis=1)
        finite = np.isfinite(d).all(axis=1)
        ok = in_range & unique & ordered & finite
        bad += int((~ok).sum())
        if ok.any():
            x = np.asarray(db[i[ok]], np.float64)          # (m, k, d)
            qq = q[ok][:, None, :]
            exact = np.sum((x - qq) ** 2, axis=2)
            scale = np.sum(x * x, axis=2) + np.sum(qq * qq, axis=2)
            err = max(err, float((np.abs(d[ok] - exact) / scale).max()))
    return bad, err


def sample_checks(ids: np.ndarray, truth: np.ndarray) -> tuple[float, float]:
    """(top1_miss, recall_miss) of answers against the exact top-k."""
    ids = np.asarray(ids, np.int64)
    truth = np.asarray(truth, np.int64)
    top1 = float((ids[:, 0] != truth[:, 0]).mean())
    hits = sum(np.intersect1d(a, t).size for a, t in zip(ids, truth))
    return top1, 1.0 - hits / truth.size


def rest_miss(ids: np.ndarray, truth: np.ndarray) -> float:
    """Share of the answers' ranks 2..k outside the exact ranking
    ``truth`` (one row per answer, as deep as the check looks)."""
    ids = np.asarray(ids, np.int64)[:, 1:]
    truth = np.asarray(truth, np.int64)
    out = sum(np.setdiff1d(a, t).size for a, t in zip(ids, truth))
    return out / ids.size if ids.size else 0.0


def control_topk(db: np.ndarray, queries: np.ndarray, k: int,
                 chunk: int = 1 << 16):
    """The reference one precision below the configuration's float32: the
    same exact search with bfloat16 operands and float32 accumulation (a
    TPU's default one-pass matmul), on the default device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(q, x, base, n_valid, best_d, best_i):
        qb, xb = q.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        dot = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
        qn = jnp.sum(qb.astype(jnp.float32) ** 2, axis=1, keepdims=True)
        xn = jnp.sum(xb.astype(jnp.float32) ** 2, axis=1)[None, :]
        col = jnp.arange(x.shape[0], dtype=jnp.int32)[None, :]
        d2 = jnp.where(col < n_valid, qn - 2.0 * dot + xn, jnp.inf)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(base + col, d2.shape)], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, k)
        return -neg, jnp.take_along_axis(cat_i, sel, axis=1)

    q = jnp.asarray(queries, jnp.float32)
    best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    n = db.shape[0]
    width = min(chunk, n)
    for s in range(0, n, width):
        x = np.asarray(db[s:s + width], np.float32)
        m = x.shape[0]
        if m < width:
            x = np.pad(x, ((0, width - m), (0, 0)))
        best_d, best_i = block(q, jnp.asarray(x), jnp.int32(s),
                               jnp.int32(m), best_d, best_i)
    return np.asarray(best_d), np.asarray(best_i, np.int64)


def checks(db, pool, window, sample, truth) -> dict:
    """Every compared number of one run.  ``sample`` indexes the
    window's answered requests drawn for the exact comparison, and
    ``truth`` holds their exact ranking, at least k ids deep."""
    from bench.traffic import LOST, OK

    ok = window.status == OK
    bad, err = answer_checks(db, pool[window.query[ok]], window.dists[ok],
                             window.ids[ok])
    ids = window.ids[sample]
    top1, miss = sample_checks(ids, truth[:, :ids.shape[1]])
    return {"lost": int((window.status == LOST).sum()), "malformed": bad,
            "dist_err": err, "top1_miss": top1, "recall_miss": miss,
            "rest_miss": rest_miss(ids, truth)}


def verdict(numbers: dict, limits: dict) -> bool:
    """True where every compared number is within its limit (a number
    that is not finite is never within)."""
    return all(np.isfinite(numbers[name]) and numbers[name] <= lim
               for name, lim in limits.items())
