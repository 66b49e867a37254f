"""Per-kernel validation: Pallas in interpret mode (on the CPU) vs pure-jnp
oracle, sweeping shapes/dtypes + hypothesis property sweeps.  Compiles for
the TPU itself are in test_tpu_compile.py."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip, unit tests still run
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.kernels import ops, ref
from repro.models import build, ssd
from repro.models.common import set_mesh_context
from repro.serve import seed_decode_cache


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


def _count_compiles(fun_name):
    """(list the compiles of ``fun_name`` are appended to, listener)."""
    compiles = []

    def count(event, duration_s, **kwargs):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kwargs.get("fun_name") == fun_name):
            compiles.append(duration_s)
    return compiles, count


def test_decode_attention_length_is_traced():
    """Every decode position reuses one compiled kernel."""
    B, S, H, hd = 1, 256, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, hd))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    compiles, count = _count_compiles("jit(decode_attention)")
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


# ---------------------------------------------------------------------------
# SSD decode-step state update, in place in a stacked cache
# ---------------------------------------------------------------------------

def _close_f32(got, want):
    """Equal to float32 rounding: a sum over the state's last axis rounds
    to its terms' scale, not its own."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * float(np.abs(want).max()))


# (layers, batch, heads, headdim, state): granite_4_0_h_micro's and
# mamba2_1_3b's widths, hymba_1_5b's, the reduced smoke configs'
@pytest.mark.parametrize("shape", [(3, 2, 64, 64, 128), (2, 4, 64, 64, 128),
                                   (3, 2, 64, 50, 16), (2, 4, 16, 8, 8)],
                         ids=["granite_b2", "granite_b4", "hymba", "smoke"])
def test_ssd_state_update_matches_the_jnp_update(shape):
    """Each layer's new state and ``y`` agree with the model's jnp update,
    every other layer of the slab is left bit for bit, and the layer index
    is traced: one compile serves every layer."""
    L, B, H, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(L * B + P), 6)
    state = jax.random.normal(ks[0], shape)
    xh = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)))
    dA = jnp.exp(-dt * jax.random.uniform(ks[3], (H,), minval=0.5,
                                          maxval=2.0))
    Bm = jax.random.normal(ks[4], (B, N))
    Cm = jax.random.normal(ks[5], (B, N))
    compiles, count = _count_compiles("jit(ssd_state_update)")
    ops.ssd_state_update.clear_cache()
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for layer in range(L):
            got, y = ops.ssd_state_update(state, jnp.int32(layer),
                                          xh * dt[..., None], dA, Bm, Cm,
                                          interpret=True)
            want, y_want = ssd._update_jnp(state, layer, xh, dt, dA, Bm, Cm)
            _close_f32(got[layer], want[layer])
            _close_f32(y, y_want)
            others = np.arange(L) != layer
            np.testing.assert_array_equal(np.asarray(got)[others],
                                          np.asarray(state)[others])
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert got.dtype == y.dtype == jnp.float32
    assert len(compiles) == 1


def _granite_smoke(**change):
    return dataclasses.replace(
        reduce_for_smoke(get_config("granite_4_0_h_micro")),
        param_dtype="float32", compute_dtype="float32", **change)


@pytest.mark.parametrize("make_cfg", [
    _granite_smoke,
    # one scanned stack of (mamba, mamba, attention) x 2: 2-layer slabs
    functools.partial(_granite_smoke, n_layers=6,
                      layer_kinds=("mamba", "mamba", "dense") * 2)],
    ids=["smoke", "smoke_scanned"])
def test_decode_steps_with_the_state_kernel_match_the_jnp_path(
        make_cfg, monkeypatch):
    """Decode steps of a small granite, the state updated by the kernel (in
    interpret mode, patched in where the CPU takes jnp), give the jnp
    path's logits and caches."""
    cfg = make_cfg()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch, prompt, steps, max_seq = 2, 8, 4, 16
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (batch, prompt + steps))
    _, caches = jax.jit(bundle.prefill)(
        params, {"tokens": jnp.asarray(tokens[:, :prompt], jnp.int32)})
    caches = seed_decode_cache(bundle, caches, batch, max_seq)

    def decode():
        # a new function each time: jit would reuse bundle.decode's trace
        step = jax.jit(lambda *args: bundle.decode(*args))
        out, c = [], caches
        for i in range(steps):
            logits, c = step(params, c, jnp.asarray(
                tokens[:, prompt + i:prompt + i + 1], jnp.int32),
                jnp.int32(prompt + i))
            out.append(np.asarray(logits))
        return np.stack(out), c

    want, want_caches = decode()
    monkeypatch.setattr(ssd, "_update_jnp", functools.partial(
        ssd._update_kernel, interpret=True))
    got, got_caches = decode()
    _close_f32(got, want)
    assert jax.tree.structure(got_caches) == jax.tree.structure(want_caches)
    for g, w in zip(jax.tree.leaves(got_caches), jax.tree.leaves(want_caches)):
        assert g.dtype == w.dtype
        _close_f32(g, w)


@pytest.mark.parametrize("state_dim,meshed,kernel", [
    (128, False, True), (128, True, False), (16, False, False)],
    ids=["lane_dense", "meshed", "hymba_d_state_16"])
def test_state_update_stages_the_kernel_where_it_updates_in_place(
        state_dim, meshed, kernel):
    """The kernel is staged for a TPU, but not under a mesh context (a
    ``pallas_call`` is not partitioned over the heads axis), nor for a state
    whose last axis is narrower than a tile's 128 lanes."""
    B, H, P, N = 2, 16, 8, state_dim
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    args = [f32((2, B, H, P, N)), jax.ShapeDtypeStruct((), jnp.int32),
            f32((B, H, P)), f32((B, H)), f32((B, H)), f32((B, N)),
            f32((B, N))]
    if meshed:   # conftest clears it after the test
        set_mesh_context(jax.make_mesh((1,), ("model",)))
    # a new function: make_jaxpr would reuse an earlier trace
    jaxpr = str(jax.make_jaxpr(lambda *a: ssd.update_state(*a))(*args))
    assert ("pallas_call" in jaxpr) == kernel
