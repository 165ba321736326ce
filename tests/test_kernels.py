"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes on CPU), plus hypothesis-driven shape fuzzing.

hypothesis is an optional dep: without it the fuzz test skips and a fixed
deterministic sweep over the same property runs instead."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels.attention.kernel import flash_attention_bhld
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.rwkv.ops import wkv6
from repro.kernels.rwkv.ref import wkv6_ref


# -------------------------------------------------------- flash attention
SWEEP = [
    # B, Hq, Hkv, Lq, Lk, D, causal, window, dtype
    (1, 1, 1, 64, 64, 32, True, 0, jnp.float32),
    (2, 4, 2, 128, 128, 64, True, 0, jnp.float32),
    (1, 2, 2, 256, 256, 32, True, 64, jnp.float32),
    (2, 2, 1, 128, 256, 64, False, 0, jnp.float32),
    (1, 4, 4, 128, 128, 128, True, 0, jnp.bfloat16),
    (1, 8, 2, 64, 128, 16, True, 32, jnp.float32),
]


@pytest.mark.parametrize("B,Hq,Hkv,Lq,Lk,D,causal,win,dtype", SWEEP)
def test_flash_attention_sweep(B, Hq, Hkv, Lq, Lk, D, causal, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Lq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Lk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Lk, D)).astype(dtype)
    out = flash_attention_bhld(q, k, v, causal=causal, window=win,
                               block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_grouped_layout_pads():
    """ops wrapper: model layout [B,Hkv,G,L,D] + non-multiple lengths."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, Hkv, G, Lq, D = 1, 2, 2, 100, 32          # 100 pads to 128
    q = jax.random.normal(ks[0], (B, Hkv, G, Lq, D))
    k = jax.random.normal(ks[1], (B, Hkv, Lq, D))
    v = jax.random.normal(ks[2], (B, Hkv, Lq, D))
    out = flash_attention(q, k, v, causal=True)
    qh = q.reshape(B, Hkv * G, Lq, D)
    ref = attention_ref(qh, k, v, causal=True).reshape(B, Hkv, G, Lq, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def _check_attention_case(lq, lk, g, hkv, win, seed):
    """Property under fuzz: kernel == reference for arbitrary grouped
    shapes, kv lengths and windows."""
    B, D = 1, 16
    Lq, Lk = lq * 32, max(lq, lk) * 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, hkv * g, Lq, D))
    k = jax.random.normal(ks[1], (B, hkv, Lk, D))
    v = jax.random.normal(ks[2], (B, hkv, Lk, D))
    out = flash_attention_bhld(q, k, v, causal=True, window=win,
                               block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=True, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


# deterministic non-hypothesis coverage of the fuzzed property
FUZZ_FALLBACK = [
    # lq, lk, g, hkv, win, seed
    (1, 1, 1, 1, 0, 0),
    (3, 1, 2, 2, 0, 1),
    (1, 3, 3, 1, 48, 2),
    (2, 3, 2, 2, 48, 3),
    (3, 3, 1, 2, 0, 4),
]


@pytest.mark.parametrize("lq,lk,g,hkv,win,seed", FUZZ_FALLBACK)
def test_flash_attention_fixed_cases(lq, lk, g, hkv, win, seed):
    _check_attention_case(lq, lk, g, hkv, win, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(lq=st.integers(1, 3), lk=st.integers(1, 3), g=st.integers(1, 3),
           hkv=st.integers(1, 2), win=st.sampled_from([0, 48]),
           seed=st.integers(0, 99))
    def test_flash_attention_fuzz(lq, lk, g, hkv, win, seed):
        _check_attention_case(lq, lk, g, hkv, win, seed)
else:
    def test_flash_attention_fuzz():
        pytest.importorskip("hypothesis")


# ----------------------------------------------------------------- wkv6
WKV_SWEEP = [
    # B, L, H, hd, chunk
    (1, 32, 1, 8, 16),
    (2, 96, 3, 16, 32),
    (1, 64, 2, 32, 32),
    (2, 80, 2, 16, 32),     # pads 80 -> 96
]


@pytest.mark.parametrize("B,L,H,hd,chunk", WKV_SWEEP)
def test_wkv6_kernel_sweep(B, L, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k, v = (jax.random.normal(ks[i], (B, L, H, hd)) for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, L, H, hd)) * 0.5)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(jax.random.PRNGKey(3), (B, H, hd, hd)) * 0.1
    y, sT = wkv6(r, k, v, logw, u, s0, chunk=chunk)
    tr = lambda a: jnp.swapaxes(a, 1, 2)
    y_ref, s_ref = wkv6_ref(tr(r), tr(k), tr(v), tr(logw), u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(tr(y_ref)),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(s_ref), atol=2e-4,
                               rtol=2e-4)


def test_wkv6_extreme_decay_stable():
    """No overflow even with near-zero decay (logw very negative) or
    near-one decay (logw ~ 0) — the log-diff scheme keeps factors <= 1."""
    B, L, H, hd = 1, 64, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    r, k, v = (jax.random.normal(ks[i], (B, L, H, hd)) for i in range(3))
    for lw_val in (-20.0, -1e-4):
        logw = jnp.full((B, L, H, hd), lw_val)
        u = jnp.zeros((H, hd))
        s0 = jnp.zeros((B, H, hd, hd))
        y, sT = wkv6(r, k, v, logw, u, s0, chunk=16)
        assert np.isfinite(np.asarray(y)).all()
        assert np.isfinite(np.asarray(sT)).all()


# ------------------------------------------------- interpret mode choice
class _Launched(Exception):
    pass


@pytest.mark.parametrize("kernel", ["flash", "wkv6"])
@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_wrappers_pick_interpret_mode_from_the_backend(
        monkeypatch, kernel, backend, interpret):
    """The model-layout wrappers interpret the kernel off the TPU and
    compile it on the TPU — never interpret there."""
    from jax.experimental import pallas as pl
    seen = {}

    def fake_pallas_call(*a, interpret, **k):
        seen["interpret"] = interpret
        raise _Launched
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(_Launched):
        if kernel == "flash":
            x = jnp.zeros((1, 1, 1, 128, 64))
            flash_attention(x, x[:, :, 0], x[:, :, 0])
        else:
            x = jnp.zeros((1, 32, 1, 16))
            wkv6(x, x, x, x, jnp.zeros((1, 16)), jnp.zeros((1, 1, 16, 16)))
    assert seen["interpret"] is interpret
