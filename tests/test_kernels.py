"""Per-kernel allclose vs the pure-jnp oracle: shape + dtype sweeps
(interpret=True executes the BlockSpec-tiled kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(seed, *shape, dtype=jnp.float32, scale=1.0):
    x = scale * jax.random.normal(jax.random.PRNGKey(seed), shape)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KvH,Dh,causal,window,bq,bk", [
    (1, 128, 2, 2, 32, True, None, 64, 64),
    (2, 256, 4, 2, 64, True, None, 128, 128),
    (1, 256, 4, 1, 64, True, 96, 128, 128),     # GQA 4:1 + window
    (2, 192, 8, 4, 32, False, None, 64, 64),    # bidirectional, ragged S
    (1, 320, 4, 4, 128, True, None, 128, 64),   # uneven blocks, pad path
])
def test_flash_attention_sweep(dtype, B, S, H, KvH, Dh, causal, window,
                               bq, bk):
    q = rand(0, B, S, H, Dh, dtype=dtype)
    k = rand(1, B, S, KvH, Dh, dtype=dtype)
    v = rand(2, B, S, KvH, Dh, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 128, 2, 8, 1, 16, 64),
    (2, 256, 4, 16, 2, 32, 64),
    (1, 256, 8, 32, 1, 64, 128),
    (2, 128, 4, 8, 4, 16, 32),      # groups == heads/1
])
def test_ssd_scan_sweep(dtype, b, s, h, p, g, n, chunk):
    x = rand(0, b, s, h, p, dtype=dtype, scale=0.5)
    dt = jax.nn.softplus(rand(1, b, s, h)).astype(jnp.float32)
    A = -jnp.exp(rand(2, h) * 0.3)
    B = rand(3, b, s, g, n, dtype=dtype, scale=0.3)
    C = rand(4, b, s, g, n, dtype=dtype, scale=0.3)
    out = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=max(TOL[dtype], 1e-4),
                               rtol=5 * TOL[dtype])


def test_flash_attention_vs_model_path():
    """Kernel path == the chunked-XLA path the models lower with."""
    from repro.nn import attention
    q, k, v = (rand(i, 2, 256, 4, 32) for i in range(3))
    a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = attention.chunked_attention(q, k, v, causal=True, chunk_q=64,
                                    chunk_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.fixture(scope="module")
def day_tables():
    """Batched day tables for a small grid that exercises throttling
    (thermal governor), puck split (two-node SKU) and the offload-only
    short schedule — the paths the fused day kernel must reproduce."""
    from repro.core import daysim
    combos, _ = daysim.build_combos(
        platforms=("aria2_display", "aria2_puck_split"),
        designs=({"name": "hot", "on_device": ("slam", "asr"),
                  "compression": 10.0},
                 {"name": "lean", "on_device": ()}),
        schedules=("commuter",),
        policies=("none", "thermal_governor", "battery_saver"))
    assert combos
    return daysim.batch_tables(combos, dt_s=60.0)


@pytest.mark.parametrize("chunk", [32, 128])
def test_day_scan_parity(day_tables, chunk):
    """Pallas fused step (interpret) vs the vmapped lax.scan oracle:
    SoC / pods / throttle level bit-exact, thermal traces to f32 ulp."""
    from repro.kernels.day_scan import day_scan
    out = day_scan(day_tables, chunk=chunk, interpret=True)
    want = ref.day_scan_ref(day_tables)
    # discrete outputs (throttle level, shutdown latch) must agree exactly
    assert np.array_equal(np.asarray(out["level"]),
                          np.asarray(want["level"]))
    np.testing.assert_array_equal(np.asarray(out["shut"]),
                                  np.asarray(want["shut"]))
    # continuous traces to f32 ulp (fused-multiply rounding differs)
    for k in ("soc", "soc_p", "pods", "t_skin", "t_skin_p",
              "drain_mw", "drain_p_mw"):
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-4, err_msg=k)


def test_day_scan_ops_dispatch(day_tables):
    """The jit'd ops wrapper returns the same pytree as the direct call,
    in interpret mode exactly when the backend is the CPU."""
    assert ops.default_interpret() == (jax.default_backend() == "cpu")
    out = ops.day_scan(day_tables)
    want = ref.day_scan_ref(day_tables)
    assert set(out) == set(want)
    np.testing.assert_allclose(np.asarray(out["soc"]),
                               np.asarray(want["soc"]),
                               rtol=1e-6, atol=1e-6)


def test_kernel_grad_smoke():
    """Kernels are used in serving; ensure at least VJP-able via ref path
    interchange (oracle equivalence implies the swap is training-safe)."""
    q, k, v = (rand(i, 1, 64, 2, 16) for i in range(3))

    def loss_ref(q):
        return jnp.sum(ref.flash_attention_ref(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_ref)(q)
    assert np.isfinite(np.asarray(g)).all()
