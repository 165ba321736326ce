"""Pallas TPU kernel for the RWKV6 WKV recurrence (data-dependent decay).

TPU adaptation of the CUDA wkv kernel (which runs a serial per-thread scan):
the sequence is processed in chunks; within a chunk the recurrence is the
*parallel* form — an intra-chunk lower-triangular matmul plus a cross-chunk
state term — so the MXU does the work. The [dk, dv] state is carried in VMEM
scratch across the sequential chunk axis of the grid.

All decay factors are exp() of differences of cumulative log-decays, which
are <= 0 by construction — numerically safe at any chunk size (same scheme
as models/ssm.wkv6_chunked, the jnp fallback this kernel is tested against).

Grid = (batch, heads, n_chunks); chunks is the sequential axis.
BlockSpecs (per step, VMEM): r/k/v/logw [1,1,C,hd]; u [1,1,hd];
state scratch [hd, hd] fp32; outputs y [1,1,C,hd] and final state [1,1,hd,hd].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sT_ref,
                 s_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)                  # [C, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)                # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)                     # [1, hd]
    S = s_scr[...]                                       # [dk, dv]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # cumsum as a lower-triangular matmul (Mosaic has no cumsum), exact
    # enough at HIGHEST precision
    cum = jax.lax.dot_general(                           # logP_t
        (t_idx >= s_idx).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    cum_shift = cum - lw                                 # logP_{t-1}
    # intra-chunk: A[t,s] = sum_d r[t,d] k[s,d] exp(cum_shift[t,d]-cum[s,d])
    # (t > s; decay diff <= 0). Diagonal gets the u bonus.
    diff = cum_shift[:, None, :] - cum[None, :, :]       # [t, s, hd]
    # the 3-D mask comes from its own iotas: Mosaic cannot reshape an i1
    # [t, s] mask to [t, s, 1]
    hd = lw.shape[1]
    strict3 = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, hd), 0) >
               jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, hd), 1))
    factor = jnp.where(strict3, jnp.exp(jnp.where(strict3, diff, 0.0)), 0.0)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * factor, axis=2)
    diag = jnp.sum(r * k * u, axis=1)                    # [t]
    A = A + jnp.where(t_idx == s_idx, diag[:, None], 0.0)
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + jax.lax.dot_general(r * jnp.exp(cum_shift), S,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    last = cum[chunk - 1:]                               # [1, hd]
    k_dec = k * jnp.exp(last - cum)
    s_scr[...] = jnp.exp(last).T * S + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sT_ref[0, 0] = s_scr[...].astype(sT_ref.dtype)


def wkv6_bhld(r, k, v, logw, u, s0, *, chunk: int = 32,
              interpret: bool | None = None):
    """r/k/v/logw: [B, H, L, hd]; u: [H, hd]; s0: [B, H, hd, hd].
    Returns (y [B,H,L,hd], sT [B,H,hd,hd]). ``interpret`` defaults to the
    backend: compiled on the TPU, interpreted (jnp) everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, L, hd = r.shape
    assert L % chunk == 0
    n_chunks = L // chunk
    kernel = functools.partial(_wkv6_kernel, chunk=chunk, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, ci: (b, h, ci, 0))
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            # u as [H, 1, hd]: a block's last two dims must be (8, 128)
            # multiples or the whole array's, which (1, hd) of [H, hd] is not
            pl.BlockSpec((1, 1, hd), lambda b, h, ci: (h, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u[:, None, :], s0)
    return y, sT
