"""Per-kernel validation: Pallas in interpret mode (on the CPU) vs pure-jnp
oracle, sweeping shapes/dtypes + hypothesis property sweeps.  Compiles for
the TPU itself are in test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip, unit tests still run
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref


def _allclose(a, b, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# streamed matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 128, 64), (128, 384, 256),
                                   (100, 60, 40)])
def test_streamed_matmul_shapes(shape, dtype):
    M, K, N = shape
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)
    out = ops.matmul(x, w, block_m=64, block_n=128, block_k=128,
                     interpret=True)
    _allclose(out, ref.matmul_ref(x, w), dtype)


@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 5))
@settings(max_examples=8, deadline=None)
def test_streamed_matmul_property(mi, ki, ni):
    # (8, 128)-aligned blocks: every example spans mi x ni output tiles
    # and accumulates over ki K-blocks
    M, K, N = 32 * mi, 128 * ki, 128 * ni
    x = jax.random.normal(jax.random.PRNGKey(mi), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(ki), (K, N), jnp.float32)
    out = ops.matmul(x, w, block_m=32, block_n=128, block_k=128,
                     interpret=True)
    _allclose(out, ref.matmul_ref(x, w), jnp.float32)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,hd", [(128, 64), (256, 128)])
def test_flash_attention(S, hd, causal, dtype):
    k = jax.random.PRNGKey(0)
    shape = (2, 3, S, hd)
    q = jax.random.normal(k, shape, dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), shape, dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    out = ops.flash_attention(q, kk, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
    _allclose(out, ref.flash_attention_ref(q, kk, v, causal=causal), dtype)


def test_flash_blocks_dont_change_result():
    k = jax.random.PRNGKey(3)
    q = jax.random.normal(k, (1, 2, 256, 64))
    kk = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 256, 64))
    a = ops.flash_attention(q, kk, v, block_q=64, block_k=128,
                            interpret=True)
    b = ops.flash_attention(q, kk, v, block_q=128, block_k=64,
                            interpret=True)
    _allclose(a, b, jnp.float32)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,chunk", [(64, 16), (128, 32), (96, 32)])
def test_ssd_scan(S, chunk, dtype):
    b, H, P, N = 2, 4, 16, 32
    k = jax.random.PRNGKey(0)
    x = (jax.random.normal(k, (b, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.3)
    B = (jax.random.normal(jax.random.PRNGKey(3), (b, S, N)) * 0.5).astype(dtype)
    C = (jax.random.normal(jax.random.PRNGKey(4), (b, S, N)) * 0.5).astype(dtype)
    out = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    r = ref.ssd_scan_kernel_ref(x, dt, A, B, C, chunk)
    scale = float(jnp.abs(r.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(out.astype(jnp.float32) - r.astype(jnp.float32)).max())
    assert err / scale < (5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_chunking_invariance():
    """Same result for different chunk sizes (associativity of the scan)."""
    b, S, H, P, N = 1, 64, 2, 8, 16
    k = jax.random.PRNGKey(7)
    x = jax.random.normal(k, (b, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(8), (b, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(9), (H,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(10), (b, S, N)) * 0.5
    C = jax.random.normal(jax.random.PRNGKey(11), (b, S, N)) * 0.5
    a = ops.ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
    bb = ops.ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    _allclose(a, bb, jnp.float32)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,length", [(256, 100), (512, 512), (512, 1)])
def test_decode_attention(S, length, dtype):
    B, H, hd = 2, 4, 64
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, H, hd), dtype)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd), dtype)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd), dtype)
    out = ops.decode_attention(q, kc, vc, length=length, block_s=128,
                               interpret=True)
    _allclose(out, ref.decode_attention_ref(q, kc, vc, length), dtype)


def test_decode_attention_length_is_traced():
    """Every decode position reuses one compiled kernel."""
    B, S, H, hd = 1, 256, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    compiles = []

    def count(event, duration_s, **kwargs):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kwargs.get("fun_name") == "jit(decode_attention)"):
            compiles.append(duration_s)

    ops.decode_attention.clear_cache()
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for length in (1, 77, 256):
            out = ops.decode_attention(q, kc, vc, jnp.int32(length),
                                       block_s=128, interpret=True)
            _allclose(out, ref.decode_attention_ref(q, kc, vc, length),
                      jnp.float32)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert len(compiles) == 1
