"""Gradient compression: int8 quantization with error feedback.

Distributed-optimization trick for the slow cross-pod axis (DESIGN.md §4):
gradients are quantized to int8 with a per-tensor absmax scale before the
data-parallel reduction; the quantization residual is carried in an error-
feedback buffer (Seide et al. / EF-SGD) so the bias vanishes over steps.

Two integration points:
  * ``make_ef_transform`` — a gradient transform inside the train step
    (models the end-to-end numerics anywhere, used by default when
    ``compress_grads`` is on; convergence-parity tested).
  * ``compressed_psum`` — an explicit shard_map collective (build the
    wrapper with ``jax.shard_map``) that
    all-gathers int8 payloads and reduces locally: 4x less cross-pod
    traffic than an fp32 all-reduce.  Used by the hand-rolled DP driver
    and exercised on the fake 8-device mesh in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["quantize_int8", "dequantize_int8", "make_ef_transform",
           "compressed_psum"]


def quantize_int8(x, axis=None):
    """int8 absmax quantization.  ``axis=None``: one scale per tensor (the
    collective payload layout).  ``axis`` (int or tuple): per-slice scales
    with ``keepdims`` so dequantization is a broadcast multiply."""
    xf = x.astype(jnp.float32)
    if axis is None:
        scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    else:
        scale = jnp.maximum(
            jnp.max(jnp.abs(xf), axis=axis, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def make_ef_transform():
    """Returns (init(grads)->buf, apply(grads, buf)->(grads', buf')).

    Matrices quantize with one scale per leading-axis row (per output
    channel): a single per-tensor absmax lets one outlier row (embedding /
    unembedding gradients) wash out every small-magnitude row's signal, and
    the extra scales are dim(row) fp32 — noise next to the int8 payload.
    Convergence parity vs fp32 is tested (test_compressed_training_parity).
    """

    def init(grads):
        return jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def apply(grads, buf):
        def one(g, e):
            corrected = g.astype(jnp.float32) + e
            axis = (tuple(range(1, corrected.ndim))
                    if corrected.ndim > 1 else None)
            q, s = quantize_int8(corrected, axis=axis)
            deq = dequantize_int8(q, s)
            return deq.astype(g.dtype), corrected - deq

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(buf)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]))

    return init, apply


def compressed_psum(x, axis_name):
    """int8 all-gather + local reduce — a compressed mean over ``axis``.

    Must run inside ``jax.shard_map``.  Payload: 1 byte/element + one fp32 scale
    per shard, vs 4 bytes/element for fp32 psum.
    """
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)            # (S, ...) int8
    ss = jax.lax.all_gather(scale, axis_name)        # (S,)
    n = qs.shape[0]
    deq = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * x.ndim)
    return deq.mean(axis=0).astype(x.dtype)
