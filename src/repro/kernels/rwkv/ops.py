"""jit'd wrapper for the WKV6 Pallas kernel, in the model's [B, L, H, hd]
layout. Pads the sequence to a chunk multiple with zero-decay padding (logw=0,
k=0 contributes nothing to state or outputs)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.rwkv.kernel import wkv6_bhld

CHUNK = 32


def wkv6(r, k, v, logw, u, s0, *, chunk: int = CHUNK):
    """r/k/v/logw: [B, L, H, hd]; u: [H, hd]; s0: [B, H, hd, hd].
    Returns (y [B, L, H, hd], sT)."""
    B, L, H, hd = r.shape
    pad = (-L) % chunk
    tr = lambda a: jnp.swapaxes(a, 1, 2)
    if pad:
        pw = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, pw)
        k = jnp.pad(k, pw)
        v = jnp.pad(v, pw)
        logw = jnp.pad(logw, pw)        # logw=0 -> decay 1: state unchanged
    y, sT = wkv6_bhld(tr(r), tr(k), tr(v), tr(logw), u, s0, chunk=chunk)
    y = tr(y)[:, :L] if pad else tr(y)
    return y, sT
