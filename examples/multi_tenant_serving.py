"""Multi-tenant serving through the cluster placement API: two different
architectures served concurrently from one physical NPU, each admitted as a
tenant via ``VNPUPolicy`` (the paper's hypervisor behind the scheduler's
``PlacementPolicy`` protocol), materialized as its own JAX submesh, with
QoS bandwidth caps — the paper's cloud scenario (§2.2/§6.3) as a running
system.

The same placement objects also feed the analytic simulator: each tenant
is scored against the NoC flows its *actual co-resident* injects, the
wiring the event-driven cluster scheduler (benchmarks/cluster_sim.py) uses
at scale.

A CPU walkthrough: it forces 8 host devices.  On a TPU, run
``python chip_smoke.py`` (one chip) or ``--four-chips`` (2x2 host).

Run: PYTHONPATH=src python examples/multi_tenant_serving.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.core import DeviceTopology
from repro.core import simulator as S
from repro.core import workloads as W
from repro.core.vmesh import virtual_mesh
from repro.models import build
from repro.models.common import clear_mesh_context
from repro.sched import TenantSpec, VNPUPolicy
from repro.serve import EngineConfig, ServeEngine


def main():
    devs = jax.devices()[:8]
    dt = DeviceTopology.from_devices(devs, (2, 4))
    policy = VNPUPolicy(dt.topo, hbm_bytes=1 << 32)

    tenants = {}
    for tid, (name, arch) in enumerate((("tenant-llama", "llama3_2_1b"),
                                        ("tenant-qwen", "qwen2_0_5b")), 1):
        spec = TenantSpec(tid=tid, model=arch, n_cores=4, arrival_s=0.0,
                          duration_s=60.0, memory_bytes=64 << 20,
                          bandwidth_cap=1 << 28)
        placement = policy.allocate(spec)
        mesh = virtual_mesh(placement.vnpu, dt)
        cfg = reduce_for_smoke(get_config(arch))
        bundle = build(cfg)
        params = bundle.init(jax.random.PRNGKey(hash(name) % 2**31))
        engine = ServeEngine(bundle, params,
                             EngineConfig(batch_size=2, max_seq=64))
        tenants[name] = (placement, mesh, engine, cfg)
        print(f"{name}: arch={arch} cores={list(placement.cores)} "
              f"bw_cap={placement.vnpu.access_counter.max} B/window")
    print(f"utilization: {policy.utilization():.0%}")

    # the scheduler's view: each tenant scored against its co-resident's
    # actual NoC flows (nothing hand-set)
    hw = S.SIM_CONFIG
    proxy = W.transformer_generic(seq=64)
    flows = {n: S.tenant_flows(proxy, p.cores, dt.topo, hw, owner=p.tid)
             for n, (p, _, _, _) in tenants.items()}
    for name, (p, _, _, _) in tenants.items():
        external = [f for o, fs in flows.items() if o != name for f in fs]
        rep = S.simulate(proxy, list(p.cores), dt.topo, hw,
                         external_flows=external)
        print(f"{name}: simulated {rep.mode} interval="
              f"{rep.interval_cycles} cyc ({rep.fps:.0f} it/s shared mesh)")

    rng = np.random.default_rng(0)
    for name, (placement, mesh, engine, cfg) in tenants.items():
        for _ in range(2):
            engine.submit(rng.integers(0, cfg.vocab_size - 1, size=8)
                          .astype(np.int32), max_new_tokens=4)
        with mesh:
            reqs = engine.run()
        clear_mesh_context()
        print(f"{name}: {[r.out_tokens for r in reqs]}  stats={engine.stats}")

    for name, (placement, _, _, _) in tenants.items():
        policy.release(placement)
    print(f"after release: utilization {policy.utilization():.0%}")
    print("OK")


if __name__ == "__main__":
    main()
