"""Gradient compression: int8 quantised all-reduce with error feedback.

At 1000+ nodes the gradient all-reduce over the slow inter-pod links
dominates step time; per-tensor-scaled int8 cuts those bytes 4× (fp32) /
2× (bf16).  Error feedback (Seide et al. 2014; Karimireddy et al. 2019)
keeps the quantisation *residual* in optimizer-state-like buffers and adds
it back before the next quantisation, restoring convergence to within noise
of the uncompressed run (validated in tests/test_compression.py).

Usage: wrap grads between value_and_grad and optimizer.update::

    comp_state = init_compression(params)
    grads, comp_state = compress_decompress(grads, comp_state)

Under jit the quantise → psum(int32) → dequantise pattern lets the SPMD
partitioner carry 1-byte payloads over the ``pod`` axis; in this framework's
step functions the compression is applied around the gradient psum
boundary (the grads produced by backward are already partially reduced over
``model`` by construction — only the data/pod reduction is compressible).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def init_compression(params: Any) -> Any:
    """Error-feedback residual buffers (zero-init, param-shaped)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compress_decompress(grads: Any, residual: Any) -> Tuple[Any, Any]:
    """Simulate the int8 all-reduce path with error feedback.

    Returns (decompressed grads to feed the optimizer, new residuals).
    The quantise/dequantise pair is exactly what each participant applies
    around the int8 collective; the residual keeps what int8 lost.
    """
    def one(g, r):
        g32 = g.astype(jnp.float32) + r
        q, scale = _quantize(g32)
        deq = _dequantize(q, scale)
        return deq.astype(g.dtype), g32 - deq

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    new_g = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_r = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_g, new_r


def compressed_psum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """int8 quantised psum for use inside shard_map collectives."""
    q, scale = _quantize(x.astype(jnp.float32))
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    smax = jax.lax.pmax(scale, axis_name)      # shared conservative scale
    return (qsum.astype(jnp.float32) * smax).astype(x.dtype)
