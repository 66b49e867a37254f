"""A whole run, the look for a chip skipped, with the timed path broken
underneath: ``correct`` has to come out false for each fault a serving
cell can have."""
import time

import jax.numpy as jnp
import pytest

import harness
from conftest import tiny_cell

SEED = 2**31 + 5
SECONDS = 1.0


def run(cell, devices):
    return harness.run_cell(cell, SEED, SECONDS, devices[:cell.chips],
                            time.perf_counter())


def test_a_sound_run_is_correct(cpu_devices):
    res = run(tiny_cell(), cpu_devices)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 4
    assert list(res)[-1] == "checks"


def test_a_token_altered_where_it_is_produced(cpu_devices, monkeypatch):
    from repro.serve.engine import ServeEngine
    finish = ServeEngine._finish_step

    def altered(self, logits, caches):
        """Slot 0 gets the token the model rates least likely."""
        tok, caches = finish(self, logits, caches)
        worst = jnp.argmin(logits[0, -1, : self.cfg.vocab_size])
        return tok.at[0].set(worst.astype(tok.dtype)), caches

    monkeypatch.setattr(ServeEngine, "_finish_step", altered)
    res = run(tiny_cell(), cpu_devices)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_a_decode_step_that_returns_its_cache_unchanged(cpu_devices,
                                                        monkeypatch):
    from repro.serve.engine import ServeEngine

    def frozen(self, params, caches, tok, pos):
        logits, _ = self.bundle.decode(params, caches, tok, pos)
        return self._finish_step(logits, caches)

    monkeypatch.setattr(ServeEngine, "_decode_step", frozen)
    res = run(tiny_cell(), cpu_devices)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("leaf", ["bias", "norm_scale"])
def test_a_bias_or_a_norm_scale_left_out(cpu_devices, monkeypatch, leaf):
    """The q/k/v bias add, or the RMSNorm's multiply by its scale, dropped
    from the model: the seeded nonzero biases and scales make it show."""
    from repro.models import attention, common
    if leaf == "bias":
        project = attention._project_qkv

        def unbiased(cfg, p, *a, **k):
            return project(cfg, {n: w for n, w in p.items()
                                 if n not in ("bq", "bk", "bv")}, *a, **k)
        monkeypatch.setattr(attention, "_project_qkv", unbiased)
    else:
        norm = common.rmsnorm

        def unscaled(x, w, eps=1e-5):
            return norm(x, jnp.ones_like(w), eps)
        monkeypatch.setattr(common, "rmsnorm", unscaled)
    res = run(tiny_cell(), cpu_devices)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_half_of_each_batch_left_out(cpu_devices, monkeypatch):
    """The later half of each batch's requests prefilled from a blank
    prompt: their answers follow no prompt of theirs."""
    from repro.serve.engine import ServeEngine
    pad = ServeEngine._pad_batch

    def halved(self, reqs):
        batch, S = pad(self, reqs)
        toks = batch["tokens"]
        batch["tokens"] = toks.at[len(reqs) // 2:len(reqs)].set(0)
        return batch, S

    monkeypatch.setattr(ServeEngine, "_pad_batch", halved)
    res = run(tiny_cell(), cpu_devices)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_requests_truncated_by_the_engine(cpu_devices, monkeypatch):
    from repro.serve.engine import ServeEngine
    serve = ServeEngine.run

    def truncating(self, max_ticks=64):
        return serve(self, max_ticks=3)

    monkeypatch.setattr(ServeEngine, "run", truncating)
    res = run(tiny_cell(), cpu_devices)
    assert res["failed"] > 0 and not res["correct"]


def test_tokens_the_engine_hands_back_in_a_list_of_its_own(
        cpu_devices, monkeypatch):
    from repro.serve.engine import ServeEngine
    serve = ServeEngine.run

    def relisting(self, max_ticks=64):
        out = serve(self, max_ticks=max_ticks)
        for r in self.queue:
            r.out_tokens = list(r.out_tokens)
        return out

    monkeypatch.setattr(ServeEngine, "run", relisting)
    res = run(tiny_cell(), cpu_devices)
    assert res["failed"] > 0 and not res["correct"]
