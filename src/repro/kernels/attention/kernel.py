"""Pallas TPU flash attention: causal + GQA + sliding window.

Grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks axis is the
sequential ("arbitrary") axis — running (m, l, acc) lives in VMEM scratch and
is carried across kv blocks. Out-of-range blocks (beyond the causal frontier
or outside the sliding window) are skipped with ``pl.when`` — on TPU the MXU
never sees them, which is where the sub-quadratic SWA FLOPs come from.

BlockSpec tiling (per grid step, VMEM):
  q    [1, 1, block_q, D]     — revisited across kv blocks
  k, v [1, 1, block_k, D]     — streamed
  o    [1, 1, block_q, D]
  scratch: m, l [block_q], acc [block_q, D] fp32

block_q/block_k default 128 — MXU-aligned (multiples of 128 on the matmul
dims; D is the lane dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, n_kv: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = ki * block_k
    skip = jnp.asarray(False)
    if causal:
        # block fully in the future of every q row it could meet
        skip = skip | (k_lo > q_lo + block_q - 1)
    if window > 0:
        # block fully before the window of the newest q row
        skip = skip | (k_lo + block_k - 1 < q_lo - window + 1)

    @pl.when(~skip)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                            block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q,
                                                            block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhld(q, k, v, *, causal: bool = True, window: int = 0,
                         scale: float | None = None, block_q: int = 128,
                         block_k: int = 128, kv_len: int | None = None,
                         interpret: bool | None = None):
    """q: [B, Hq, Lq, D]; k/v: [B, Hkv, Lk, D] with Hq % Hkv == 0.

    Lq/Lk must be multiples of block_q/block_k (ops.py pads). ``kv_len``
    masks padding at the tail of k/v. ``interpret`` defaults to the
    backend: compiled on the TPU, interpreted (jnp) everywhere else.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, Lq, D = q.shape
    _, Hkv, Lk, _ = k.shape
    assert Hq % Hkv == 0 and Lq % block_q == 0 and Lk % block_k == 0
    G = Hq // Hkv
    n_kv = Lk // block_k
    scale = D ** -0.5 if scale is None else scale
    kv_len = Lk if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv, kv_len=kv_len)
    grid = (B, Hq, Lq // block_q, n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
