"""Batched candidate-tile L2 + streaming top-k Pallas TPU kernel.

The gathered-candidate counterpart of `l2_topk`: each query row carries
its **own** candidate list — IVF probe steps score ``(B, C, d)`` bucket
gathers, the forest rerank scores leaf unions — so the contraction is a
batched matvec per query rather than one shared db matmul.  Fusing the
distance + merge here is what removes the materialized ``(B, C)``
distance matrix from the sharded IVF/forest locals.

Grid: (B_tiles, C_tiles), C innermost, running top-k in the revisited
output block.  The kernel optionally *continues* a running best list
(``best_d``/``best_i`` operands seed the step-0 state), which is how the
IVF ``lax.scan`` over probe steps chains one kernel launch per probe
without re-ranking from scratch.

Tiles are derived from the shapes, not fixed: one ``(bq, bc, d)``
candidate block must fit ``VMEM_BLOCK_BYTES`` (it is double-buffered and
the kernel keeps a same-sized temporary, so three of them share the
chip's scoped VMEM).  ``bc`` is the whole candidate width when a
minimum-height block fits, else the fewest 128-multiple chunks that do
(the ``(8, 128)`` block rule: the ``(bq, bc)`` id block's last dim is a
multiple of 128 or the full width); ``bq`` is then the most 8-row query
groups that fit, at most 64.

Ids are caller-supplied (bucket slot ids / global entity ids), already
arbitrary-order; ``id < 0`` marks a dead candidate (empty bucket slot or
grid pad) and scores +inf.  Ties break on the (distance, id) pair (see
``common.merge_topk``); a candidate duplicated *with identical distance*
is emitted once, not twice — the jnp oracle used on the CPU dispatch
path keeps ``lax.top_k`` column-order semantics instead, which agree
whenever ids are distinct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import HIGHEST, INF, merge_topk

MAX_BQ = 64
VMEM_BLOCK_BYTES = 4 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def candidate_tiles(B: int, C: int, D: int, itemsize: int = 4):
    """(bq, bc) for a (B, C, D) candidate tensor: the largest tiles whose
    ``(bq, bc, D)`` block stays within ``VMEM_BLOCK_BYTES``, where VMEM
    pads the lane dim D to 128 and the sublane dim bc to 8."""
    lane = _round_up(D, 128) * itemsize
    n_c = -(-(_round_up(C, 8) * 8 * lane) // VMEM_BLOCK_BYTES)
    bc = C if n_c <= 1 else _round_up(-(-C // n_c), 128)
    fit = VMEM_BLOCK_BYTES // (_round_up(bc, 8) * lane) // 8 * 8
    bq = max(8, min(MAX_BQ, fit, _round_up(B, 8)))
    return bq, bc


def _kernel(q_ref, v_ref, i_ref, b0d_ref, b0i_ref, bd_ref, bi_ref,
            *, k: int):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        bd_ref[...] = b0d_ref[...]
        bi_ref[...] = b0i_ref[...]

    q = q_ref[...].astype(jnp.float32)            # (BQ, D)
    vecs = v_ref[...].astype(jnp.float32)         # (BQ, BC, D)
    ids = i_ref[...]                              # (BQ, BC) int32

    # same expansion as core.brute.batched_l2sq, batched on the MXU
    vn = jnp.sum(vecs * vecs, axis=-1)            # (BQ, BC)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)   # (BQ, 1)
    dots = jax.lax.dot_general(
        vecs, q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )                                             # (BQ, BC)
    d2 = vn - 2.0 * dots + qn
    d2 = jnp.where(ids >= 0, d2, INF)

    new_d, new_i = merge_topk(bd_ref[...], bi_ref[...], d2, ids, k)
    bd_ref[...] = new_d
    bi_ref[...] = new_i


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bc", "interpret")
)
def candidate_topk_pallas(
    queries: jnp.ndarray,        # (B, D)
    vecs: jnp.ndarray,           # (B, C, D) per-query candidate vectors
    ids: jnp.ndarray,            # (B, C) int32, < 0 = dead slot
    k: int = 10,
    *,
    best_d: jnp.ndarray | None = None,   # (B, k) carried running best
    best_i: jnp.ndarray | None = None,
    bq: int | None = None,
    bc: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (dists (B, k) ascending fp32, ids (B, k) int32).

    When ``best_d``/``best_i`` are given the result is the merge of the
    carried list with the candidate tile (the IVF probe-chain pattern);
    otherwise the list starts from the ``(inf, -1)`` sentinel.  ``k``
    may exceed C — unfilled slots return the sentinel.  ``bq``/``bc``
    override the derived tiles (:func:`candidate_tiles`).
    """
    B, C, D = vecs.shape
    auto_bq, auto_bc = candidate_tiles(B, C, D, vecs.dtype.itemsize)
    bq = min(bq or auto_bq, max(8, B))
    bc = min(bc or auto_bc, max(8, C))
    grid_b = -(-B // bq)
    grid_c = -(-C // bc)
    qp = jnp.pad(queries.astype(jnp.float32), ((0, grid_b * bq - B), (0, 0)))
    vp = jnp.pad(vecs, ((0, grid_b * bq - B), (0, grid_c * bc - C), (0, 0)))
    ip = jnp.pad(ids.astype(jnp.int32),
                 ((0, grid_b * bq - B), (0, grid_c * bc - C)),
                 constant_values=-1)
    # repro: allow(missing-static-argnames): branches on operand PRESENCE (None vs array) — pytree structure jit already specializes on; static_argnames would reject array operands
    if best_d is None:
        b0d = jnp.full((grid_b * bq, k), INF, jnp.float32)
        b0i = jnp.full((grid_b * bq, k), -1, jnp.int32)
    else:
        b0d = jnp.pad(best_d.astype(jnp.float32),
                      ((0, grid_b * bq - B), (0, 0)), constant_values=INF)
        b0i = jnp.pad(best_i.astype(jnp.int32),
                      ((0, grid_b * bq - B), (0, 0)), constant_values=-1)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(grid_b, grid_c),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bc, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid_b * bq, k), jnp.float32),
            jax.ShapeDtypeStruct((grid_b * bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(qp, vp, ip, b0d, b0i)
    return out[0][:B], out[1][:B]
