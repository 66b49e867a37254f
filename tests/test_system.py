"""End-to-end behaviour tests: training convergence, checkpoint/restart,
serving, data determinism, gradient compression — system-level invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.data import DataConfig, make_batch
from repro.models import build
from repro.train import (AdamWConfig, TrainConfig, init_state,
                         make_train_step, train_loop)


def _bundle(arch="llama3_2_1b"):
    cfg = reduce_for_smoke(get_config(arch))
    return build(cfg), cfg


class TestTraining:
    def test_loss_decreases(self):
        bundle, cfg = _bundle()
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                          global_batch=8)
        tcfg = TrainConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=2))

        def it():
            s = 0
            while True:
                yield {k: jnp.asarray(v)
                       for k, v in make_batch(dcfg, s).items()}
                s += 1

        state, hist = train_loop(bundle, tcfg, it(), n_steps=30,
                                 key=jax.random.PRNGKey(0), log_every=1)
        assert hist[-1]["loss"] < hist[0]["loss"] * 0.8
        assert np.isfinite(hist[-1]["loss"])

    def test_grad_accum_close_to_full_batch(self):
        bundle, cfg = _bundle()
        params = bundle.init(jax.random.PRNGKey(0))
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8)
        batch = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}
        s1 = init_state(params, AdamWConfig(lr=1e-3))
        s2 = init_state(params, AdamWConfig(lr=1e-3))
        step1 = jax.jit(make_train_step(bundle.loss,
                                        TrainConfig(opt=AdamWConfig(lr=1e-3))))
        step2 = jax.jit(make_train_step(
            bundle.loss, TrainConfig(opt=AdamWConfig(lr=1e-3), grad_accum=2)))
        s1, _ = step1(s1, batch)
        s2, _ = step2(s2, batch)
        d = [float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                   b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(s1["params"]),
                             jax.tree.leaves(s2["params"]))]
        assert max(d) < 2e-2

    def test_int8_moments_close_to_fp32(self):
        bundle, cfg = _bundle("qwen2_0_5b")
        params = bundle.init(jax.random.PRNGKey(0))
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4)
        batch = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}
        outs = {}
        for md in ("float32", "int8"):
            tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, moment_dtype=md))
            step = jax.jit(make_train_step(bundle.loss, tcfg))
            st = init_state(params, tcfg.opt)
            for _ in range(3):
                st, m = step(st, batch)
            outs[md] = float(m["loss"])
        assert abs(outs["int8"] - outs["float32"]) < 0.2


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        from repro.checkpoint import (latest_step, restore_checkpoint,
                                      save_checkpoint)
        bundle, _ = _bundle()
        params = bundle.init(jax.random.PRNGKey(0))
        opt = AdamWConfig(lr=1e-3)
        state = init_state(params, opt)
        save_checkpoint(str(tmp_path), state, step=7)
        assert latest_step(str(tmp_path)) == 7
        like = jax.eval_shape(lambda: init_state(
            bundle.init(jax.random.PRNGKey(0)), opt))
        restored, step = restore_checkpoint(str(tmp_path), like)
        assert step == 7
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_resume_continues_training(self, tmp_path):
        from repro.checkpoint import restore_checkpoint
        bundle, cfg = _bundle()
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4)
        tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=1))

        def it(start=0):
            s = start
            while True:
                yield {k: jnp.asarray(v)
                       for k, v in make_batch(dcfg, s).items()}
                s += 1

        state, _ = train_loop(bundle, tcfg, it(), n_steps=4,
                              key=jax.random.PRNGKey(0),
                              checkpoint_dir=str(tmp_path),
                              checkpoint_every=4)
        like = jax.eval_shape(lambda: init_state(
            bundle.init(jax.random.PRNGKey(0)), tcfg.opt))
        restored, step = restore_checkpoint(str(tmp_path), like)
        assert step == 4
        state2, hist = train_loop(bundle, tcfg, it(4), n_steps=2,
                                  state=restored)
        assert int(state2["step"]) == 6


class TestData:
    def test_determinism_and_host_sharding(self):
        d0 = DataConfig(vocab_size=1000, seq_len=64, global_batch=8,
                        host_index=0, host_count=2)
        d1 = dataclasses.replace(d0, host_index=1)
        a = make_batch(d0, 5)["tokens"]
        b = make_batch(d0, 5)["tokens"]
        c = make_batch(d1, 5)["tokens"]
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
        assert a.shape == (4, 64)


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        from repro.train.optimizer import dequantize_q8, quantize_q8
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 384)) * 3.0
        q = quantize_q8(x)
        r = dequantize_q8(q, 384)
        err = jnp.max(jnp.abs(r - x))
        assert float(err) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6

    def test_error_feedback_preserves_mean_gradient(self):
        from repro.parallel import make_error_feedback_compressor
        compress, init = make_error_feedback_compressor()
        g = {"w": jnp.full((4, 256), 1e-3)}
        r = init(g)
        total = jnp.zeros((4, 256))
        for _ in range(8):
            gq, r = compress(g, r)
            total = total + gq["w"]
        np.testing.assert_allclose(np.asarray(total / 8),
                                   np.asarray(g["w"]), atol=3e-4)

    def test_wire_ratio_near_4x(self):
        from repro.parallel import compression_ratio
        g = {"a": jnp.zeros((1024, 1024)), "b": jnp.zeros((512, 512))}
        assert 3.5 < compression_ratio(g) <= 4.0


class TestServing:
    def test_engine_end_to_end(self):
        from repro.serve import EngineConfig, ServeEngine
        bundle, cfg = _bundle()
        params = bundle.init(jax.random.PRNGKey(0))
        eng = ServeEngine(bundle, params,
                          EngineConfig(batch_size=2, max_seq=64))
        rng = np.random.default_rng(0)
        for _ in range(2):
            eng.submit(rng.integers(0, cfg.vocab_size - 1, size=8
                                    ).astype(np.int32), max_new_tokens=4)
        reqs = eng.run()
        assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
        assert all(0 <= t < cfg.vocab_size
                   for r in reqs for t in r.out_tokens)

    def test_submit_rejects_cache_overrun(self):
        from repro.serve import EngineConfig, ServeEngine
        bundle, cfg = _bundle()
        eng = ServeEngine(bundle, None, EngineConfig(batch_size=1, max_seq=16))
        eng.submit(np.zeros(8, np.int32), max_new_tokens=9)   # fills 16
        with pytest.raises(ValueError, match="max_seq"):
            eng.submit(np.zeros(8, np.int32), max_new_tokens=10)

    def test_decode_reuses_one_compile(self):
        """The engine's AOT compile serves every decode step."""
        from repro.serve import EngineConfig, ServeEngine
        bundle, cfg = _bundle()
        params = bundle.init(jax.random.PRNGKey(0))
        eng = ServeEngine(bundle, params, EngineConfig(batch_size=2,
                                                       max_seq=32))
        eng.compile(prompt_len=8)
        assert eng.stats["compiles"] == 2
        for _ in range(2):
            eng.submit(np.arange(8, dtype=np.int32), max_new_tokens=5)
        eng.run()
        assert eng.stats["compiles"] == 2


class TestLaunch:
    def test_compile_cache_env_wins(self, monkeypatch, tmp_path):
        from repro.launch import compile_cache as cc
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert cc.compile_cache_dir() == str(tmp_path)
        assert cc.enable_compile_cache() == str(tmp_path)
        assert calls == []            # JAX reads the variable itself

    def test_compile_cache_default_is_fixed_in_checkout(self, monkeypatch):
        from pathlib import Path
        from repro.launch import compile_cache as cc
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        a, b = cc.compile_cache_dir(), cc.compile_cache_dir()
        assert a == b
        root = Path(__file__).resolve().parent.parent
        assert Path(a).parent == root
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *args: calls.append(args))
        assert cc.enable_compile_cache() == a
        assert calls == [("jax_compilation_cache_dir", a)]

    def test_constraint_without_mesh_raises(self):
        from repro.models.common import (clear_mesh_context,
                                         set_activation_rules,
                                         with_logical_constraint)
        x = jnp.ones((4, 4))
        assert with_logical_constraint(x, "batch", None) is x
        set_activation_rules({"batch": ("data",)})
        try:
            with pytest.raises(RuntimeError, match="mesh"):
                jax.jit(lambda a: with_logical_constraint(a, "batch", None)
                        )(x)
        finally:
            clear_mesh_context()


class TestDeviceBinding:
    def test_tpu_devices_bind_by_coords(self):
        """Core (row, col) is the chip at coords (x=col, y=row), whatever
        order the devices are listed in."""
        import random
        from types import SimpleNamespace
        from repro.core import DeviceTopology
        devs = [SimpleNamespace(id=i, coords=(x, y, 0), core_on_chip=0)
                for i, (x, y) in enumerate((x, y) for y in range(2)
                                           for x in range(3))]
        random.Random(0).shuffle(devs)
        dt = DeviceTopology.from_devices(devs)
        assert sorted(dt.topo.coords.values()) == [
            (r, c) for r in range(2) for c in range(3)]
        for node, (r, c) in dt.topo.coords.items():
            assert dt.device_for(node).coords[:2] == (c, r)

    def test_devices_without_coords_bind_in_order(self):
        from repro.core import DeviceTopology
        devs = jax.devices()[:4]
        dt = DeviceTopology.from_devices(devs, (2, 2))
        assert [dt.device_for(i) for i in range(4)] == list(devs)
