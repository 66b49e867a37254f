"""Elastic failover drill through the cluster placement API:
train -> checkpoint -> 'device failure' -> policy-driven live migration
(similar-topology remap avoiding the dead core) -> restore on the new
submesh -> keep training -> 'device repaired' -> capacity returns to the
free pool.

The paper's topology mapper is the failover mechanism: ``VNPUPolicy.migrate``
re-runs minTopologyEditDistance over the survivors (the same call the
cluster scheduler uses for defragmentation — failure is just a migration
with a forbidden core) and the checkpoint reshards onto whatever submesh
came back.  The pause charged in the cluster simulator is exactly what this
drill performs for real: routing-table reinstall + weight re-warm from the
checkpoint, with the RTT (global memory) preserved.

A CPU walkthrough: it forces 8 host devices.  On a TPU, run
``python chip_smoke.py`` (one chip) or ``--four-chips`` (2x2 host).

Run: PYTHONPATH=src python examples/elastic_failover.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.core import DeviceTopology
from repro.core import simulator as S
from repro.core.vmesh import virtual_mesh
from repro.data import DataConfig, make_batch
from repro.models import build
from repro.sched import TenantSpec, VNPUPolicy
from repro.train import AdamWConfig, TrainConfig, init_state, make_train_step


def main():
    devs = jax.devices()[:8]
    dt = DeviceTopology.from_devices(devs, (2, 4))
    policy = VNPUPolicy(dt.topo, hbm_bytes=1 << 32)
    spec = TenantSpec(tid=1, model="qwen2_0_5b", n_cores=4, arrival_s=0.0,
                      duration_s=600.0)
    placement = policy.allocate(spec)
    mesh = virtual_mesh(placement.vnpu, dt)
    print(f"tenant on cores {list(placement.cores)}")

    cfg = reduce_for_smoke(get_config("qwen2_0_5b"))
    bundle = build(cfg)
    tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=2))
    step = jax.jit(make_train_step(bundle.loss, tcfg))
    state = init_state(bundle.init(jax.random.PRNGKey(0)), tcfg.opt)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)

    def batch_at(i):
        return {k: jnp.asarray(v) for k, v in make_batch(dcfg, i).items()}

    with mesh:
        for i in range(3):
            state, m = step(state, batch_at(i))
    print(f"trained 3 steps, loss={float(m['loss']):.3f}")

    ckpt = tempfile.mkdtemp(prefix="elastic-")
    save_checkpoint(ckpt, state, step=3)
    print(f"checkpointed at step 3 -> {ckpt}")

    # ---- simulated failure of one allocated device --------------------
    dead = placement.cores[0]
    print(f"!! device at core {dead} failed")
    policy.mark_failed([dead])       # quarantine: never reallocated
    placement, moved = policy.migrate(placement, avoid=[dead])
    assert moved and dead not in placement.cores
    assert dead not in policy.free_cores()
    pause = policy.migration_cycles(placement, 64 << 20,
                                    S.SIM_CONFIG.hbm_bytes_per_cycle)
    print(f"migrated: new cores {list(placement.cores)} "
          f"(ted={placement.vnpu.ted}, modeled pause "
          f"{pause / S.SIM_CONFIG.freq_hz * 1e3:.2f} ms)")
    mesh = virtual_mesh(placement.vnpu, dt)

    like = jax.eval_shape(lambda: init_state(
        bundle.init(jax.random.PRNGKey(0)), tcfg.opt))
    state, start = restore_checkpoint(ckpt, like)
    print(f"restored step {start} onto the new submesh")
    with mesh:
        for i in range(start, start + 2):
            state, m = step(state, batch_at(i))
    print(f"resumed training, step={int(state['step'])}, "
          f"loss={float(m['loss']):.3f}")

    # ---- the device comes back from maintenance -----------------------
    # repair is the other half of the chaos plane: the quarantined core
    # rejoins the free pool (the scheduler's REPAIR event drives this
    # same call and then drains its admission queue)
    policy.mark_repaired([dead])
    assert dead in policy.free_cores()
    spare = policy.allocate(TenantSpec(tid=2, model="qwen2_0_5b",
                                       n_cores=4, arrival_s=0.0,
                                       duration_s=60.0))
    print(f"core {dead} repaired; new tenant placed on "
          f"{list(spare.cores)} using the restored capacity")
    policy.release(spare)
    print("OK")


if __name__ == "__main__":
    main()
