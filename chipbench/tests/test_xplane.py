"""The trace reduction on a small trace recorded on a TPU v5e serving
qwen2_0_5b: a ``--trace 1 --seconds 1`` run of the chat cell, cut to its
first 0.25 s of device activity and gzipped."""
import numpy as np
import pytest

import xplane
from conftest import BENCH

DATA = BENCH / "tests" / "data"
ONE_CHIP = DATA / "chat_1chip.xplane.pb.gz"


def naive_busy_s(dev, step_ns=100):
    """Busy time of one device on a grid of ``step_ns``: the union drawn
    the slow way."""
    lo = min(o[0] for o in dev.ops)
    hi = max(o[1] for o in dev.ops)
    grid = np.zeros(int((hi - lo) // step_ns) + 2, bool)
    for s, e, _, _ in dev.ops:
        grid[int((s - lo) // step_ns):int(np.ceil((e - lo) / step_ns))] = True
    return grid.sum() * step_ns * 1e-9


def test_busy_time_is_the_union_of_operations():
    devices, spans = xplane.load(ONE_CHIP)
    r = xplane.reduce(ONE_CHIP)
    assert r.n_devices == 1 == len(devices)
    naive = np.mean([naive_busy_s(d) for d in devices])
    assert r.busy_s == pytest.approx(naive, rel=2e-3)
    assert 0 < r.busy_s <= r.window_s
    assert 0 <= r.idle_share_serving < 1
    assert r.decode_steps > 0
    assert {n for _, _, n in spans} <= {"engine_run", "wait", "submit"}


def test_breakdown_names_operations_and_gaps():
    r = xplane.reduce(ONE_CHIP)
    assert 0 < len(r.device_ops) <= xplane.TOP
    assert all(" = " not in name and "/" in name for name, _ in r.device_ops)
    secs = [s for _, s in r.device_ops]
    assert secs == sorted(secs, reverse=True) and secs[0] <= r.busy_s
    assert not any(name.split("/")[1].startswith("while")
                   for name, _ in r.device_ops)
    assert all(label in ("submit", "wait", "engine_run")
               for label, _ in r.idle_gaps)


def test_control_flow_is_left_out_of_the_breakdown():
    """A while loop enclosing two fusions: busy is their union, and the
    breakdown lists the fusions alone."""
    mods = [(0, 100_000, "jit__decode_step")]
    ops = [(0, 100_000, "while.1"), (10_000, 40_000, "fusion.2"),
           (50_000, 90_000, "fusion.3")]
    dev = xplane.Device([(s, e, n, bool(xplane.CONTROL_FLOW.match(n)))
                         for s, e, n in ops], mods)
    r = xplane.reduce_devices([dev], [])
    assert r.busy_s == pytest.approx(100e-6)
    assert [n for n, _ in r.device_ops] == ["jit__decode_step/fusion.3",
                                            "jit__decode_step/fusion.2"]
    assert r.decode_steps == 1


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce(path)
