"""jit'd wrapper around the Pallas flash-attention kernel.

Accepts the model's grouped layout [B, Hkv, G, L, D], pads sequence lengths
to block multiples, dispatches to the kernel (compiled on the TPU; elsewhere
interpreted, so the kernel body runs as jnp ops for validation on the CPU),
and restores the layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_bhld


def _pad_to(x, mult: int, axis: int):
    L = x.shape[axis]
    pad = (-L) % mult
    if pad == 0:
        return x, L
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), L


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128):
    """q: [B, Hkv, G, Lq, D]; k/v: [B, Hkv, Lk, D] (the model's layout).
    Returns [B, Hkv, G, Lq, Dv]."""
    B, Hkv, G, Lq, D = q.shape
    qh = q.reshape(B, Hkv * G, Lq, D)
    qh, Lq0 = _pad_to(qh, block_q, 2)
    kh, Lk0 = _pad_to(k, block_k, 2)
    vh, _ = _pad_to(v, block_k, 2)
    out = flash_attention_bhld(qh, kh, vh, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               kv_len=Lk0)
    out = out[:, :, :Lq0]
    return out.reshape(B, Hkv, G, Lq0, out.shape[-1])
