"""Meshed tests (8 host devices, 2x4): sharded train/forward equivalence,
the attention core's shape-derived sharding, sequence-parallel SSD
exactness, vNPU->Mesh integration, elastic remap, simulator sanity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.launch.mesh import make_test_mesh
from repro.models import build
from repro.models.common import (clear_mesh_context, set_activation_rules,
                                 set_mesh_context)
from repro.parallel import seq_parallel_ssd, sharding as shd

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 host devices")


def _mesh():
    return make_test_mesh((2, 4), ("data", "model"))


def _meshed_forward(cfg, seq_len):
    """(unmeshed logits, meshed logits, the meshed forward's lowered text)
    of ``cfg`` on a batch of 4 x ``seq_len`` tokens, over the 2x4 mesh."""
    mesh = _mesh()
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)
    clear_mesh_context()
    params = bundle.init(key)
    batch = {"tokens": jax.random.randint(key, (4, seq_len), 0, 255)}
    ref = np.asarray(bundle.forward(params, batch), np.float32)

    set_mesh_context(mesh, shd.batch_axes(mesh))
    set_activation_rules(shd.activation_rules(mesh))
    pshard = shd.named_shardings(
        mesh, shd.param_specs(bundle.param_logical_axes(),
                              shd.param_rules(mesh)))
    bshard = shd.named_shardings(mesh, shd.batch_specs(batch, mesh))
    with mesh:
        fwd = jax.jit(bundle.forward, in_shardings=(pshard, bshard))
        lowered = fwd.lower(params, batch).as_text()
        out = fwd(jax.device_put(params, pshard),
                  jax.device_put(batch, bshard))
    return ref, np.asarray(out, np.float32), lowered


class TestShardedEquivalence:
    @pytest.mark.parametrize("arch", ["llama3_2_1b", "hymba_1_5b"])
    def test_meshed_forward_matches_local(self, arch):
        cfg = dataclasses.replace(reduce_for_smoke(get_config(arch)),
                                  d_model=64, vocab_size=256,
                                  param_dtype="float32")
        ref, out, _ = _meshed_forward(cfg, 64)
        np.testing.assert_allclose(ref, out, rtol=2e-3, atol=2e-3)

    # model axis 4, 4 query heads: S=64 divides, so sequence shards; S=62
    # does not, so head shards where the 4 KV heads divide, else none
    @pytest.mark.parametrize("branch,seq_len,kv_heads", [
        ("seq", 64, 2), ("heads", 62, 4), ("replicated", 62, 2)])
    def test_attention_core_sharding_follows_shapes(self, branch, seq_len,
                                                    kv_heads):
        cfg = dataclasses.replace(
            reduce_for_smoke(get_config("llama3_2_1b")), d_model=64,
            vocab_size=256, n_kv_heads=kv_heads, param_dtype="float32")
        ref, out, lowered = _meshed_forward(cfg, seq_len)
        np.testing.assert_allclose(ref, out, rtol=2e-3, atol=2e-3)
        gathers = "all_gather" in lowered      # seq's K/V all-gather
        shard_map = "manual_computation" in lowered
        assert (gathers, shard_map) == {"seq": (True, True),
                                        "heads": (False, True),
                                        "replicated": (False, False)}[branch]

    def test_moe_ep_matches_local_when_no_drops(self):
        from repro.models.moe import moe_forward, moe_init
        mesh = _mesh()
        cfg = dataclasses.replace(reduce_for_smoke(get_config(
            "deepseek_moe_16b")), d_model=64, capacity_factor=16.0)
        p, _ = moe_init(cfg, jax.random.PRNGKey(1), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 64), jnp.float32)
        y_ref, _ = moe_forward(cfg, p, x, mesh=None)
        specs = {"router": P(), "wg": P("model", None, None),
                 "wu": P("model", None, None), "wd": P("model", None, None),
                 "shared_wg": P(None, "model"), "shared_wu": P(None, "model"),
                 "shared_wd": P("model", None)}
        pm = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in p.items()}
        xm = jax.device_put(x, NamedSharding(mesh, P(("data",), None, None)))
        with mesh:
            y, _ = jax.jit(lambda pp, xx: moe_forward(cfg, pp, xx, mesh=mesh)
                           )(pm, xm)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                                   rtol=1e-4, atol=1e-4)


class TestSeqParallel:
    def test_sp_ssd_matches_serial(self):
        from repro.models.ssd import ssd_scan_ref
        mesh = _mesh()
        b, S, H, Pd, N = 1, 128, 4, 8, 16
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (b, S, H, Pd)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                               (b, S, H)))
        A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)) * 0.3)
        B = jax.random.normal(jax.random.PRNGKey(3), (b, S, 1, N)) * 0.5
        C = jax.random.normal(jax.random.PRNGKey(4), (b, S, 1, N)) * 0.5
        ref = ssd_scan_ref(x, dt, A, B, C, 16)
        with mesh:
            out = seq_parallel_ssd(x, dt, A, B, C, chunk=16, mesh=mesh,
                                   axis="data")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)


class TestVMesh:
    def test_tenant_mesh_and_elastic_remap(self):
        from repro.core import (DeviceTopology, Hypervisor, allocate_tenant,
                                elastic_remap, mesh_2d)
        devs = jax.devices()[:8]
        dt = DeviceTopology.from_devices(devs, (2, 4))
        hyp = Hypervisor(dt.topo, hbm_bytes=1 << 30)
        tenant = allocate_tenant(hyp, dt, mesh_2d(2, 2, base_id=100))
        assert tenant.mesh.devices.shape == (2, 2)
        # run a tiny sharded computation on the tenant mesh
        x = jnp.arange(8.0).reshape(4, 2)
        y = jax.jit(lambda a: a * 2,
                    in_shardings=NamedSharding(tenant.mesh,
                                               P("data", "model")),
                    )(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
        # kill one allocated node; remap must avoid it
        dead = next(iter(tenant.vnpu.p_cores))
        t2 = elastic_remap(hyp, dt, tenant, [dead])
        assert dead not in t2.vnpu.p_cores
        assert t2.mesh.devices.shape == (2, 2)

    def test_tenants_get_disjoint_devices(self):
        from repro.core import DeviceTopology, Hypervisor, allocate_tenant, \
            mesh_2d
        devs = jax.devices()[:8]
        dt = DeviceTopology.from_devices(devs, (2, 4))
        hyp = Hypervisor(dt.topo, hbm_bytes=1 << 30)
        t1 = allocate_tenant(hyp, dt, mesh_2d(1, 4, base_id=50))
        t2 = allocate_tenant(hyp, dt, mesh_2d(1, 4, base_id=60))
        d1 = {d.id for d in t1.mesh.devices.flat}
        d2 = {d.id for d in t2.mesh.devices.flat}
        assert not (d1 & d2)


class TestSimulatorSanity:
    def test_paper_trends_hold(self):
        """The headline directions of §6 must hold in the simulator."""
        from repro.core import simulator as S, workloads as W
        hw = S.SIM_CONFIG
        topo = hw.topo()
        tra = W.get_workload("transformer")
        r_df = S.simulate(tra, [0, 1, 6, 7], topo, hw)
        r_uv = S.simulate(tra, [0, 1, 6, 7], topo, hw, comm="uvm")
        assert r_df.fps / r_uv.fps > 1.5          # Fig 15 direction
        g = W.get_workload("gpt2_large")
        r_v = S.simulate(g, list(range(36)), topo, hw)
        r_m = S.simulate(g, list(range(36)), topo, hw, tdm_physical=24)
        assert 1.5 < r_v.fps / r_m.fps < 2.5      # Fig 16 (paper 1.92x)
        d_page = S.simulate_weight_dma(256 << 20, hw, translation="page",
                                       tlb_entries=4, bw_share=1 / 36)
        d_rng = S.simulate_weight_dma(256 << 20, hw, translation="range",
                                      tlb_entries=4, bw_share=1 / 36)
        assert d_page.overhead > 0.1              # Fig 14: page ~20%
        assert d_rng.overhead < 0.043             # Fig 14: range <= 4.3%

    def test_trace_driven_matches_pattern_claims(self):
        """Real vchunk TLB structures driven by a Pattern-1/2/3 trace."""
        from repro.core import simulator as S
        hw = S.SIM_CONFIG
        # 7 MB blob -> 3 buddy ranges (4+2+1); 2-entry TLB misses on the
        # wrap-around so Pattern-3's last_v actually fires
        r = S.simulate_weight_dma(7 << 20, hw, translation="range",
                                  tlb_entries=2, n_iterations=3,
                                  trace_driven=True)
        assert r.stats is not None
        # iteration-periodic trace: last_v learned after iteration 1
        assert r.stats.last_v_hits >= 1
        assert r.overhead < 0.01
