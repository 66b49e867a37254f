"""The engine layer's readers, queue_wait_p95_ms and decode_slot_use: their
arithmetic on a synthetic run, a whole run on the CPU, and nothing read
from an engine that stamps and counts neither."""
import time
import types

import numpy as np
import pytest

import harness
import work
from conftest import tiny_cell

SEED = 2**31 + 11


def req(submitted_at=None, admitted_at=None):
    r = types.SimpleNamespace(out_tokens=harness.StampList(), done=True,
                              rid=0)
    if submitted_at is not None:
        r.submitted_at, r.admitted_at = submitted_at, admitted_at
    return harness.Served(r, 0.0, 0.0, 1, np.zeros(16, np.int32))


def run_of(served, stats):
    cell = tiny_cell()
    return harness.Run(cell=cell, shape=work.Shape.of(cell.model),
                       setup_s=1.0, t0=0.0, window_s=10.0, served=served,
                       stats=stats, device_kind="TPU v5 lite")


STATS = {"decode_steps": 0, "prefill_s": 0.0, "decode_s": 0.0,
         "tokens_out": 0}


def test_queue_wait_is_the_p95_of_admission_less_submission():
    # waits of 0, 10, ..., 990 ms
    served = [req(100.0 + k, 100.0 + k + k * 0.01) for k in range(100)]
    run = run_of(served, STATS)
    assert harness.read_metric("queue_wait_p95_ms", run) == pytest.approx(
        np.percentile(np.arange(100) * 10.0, 95))


def test_decode_slot_use_counts_emitting_slots():
    """Two batches of the tiny cell's 8 slots: 6 and 2 requests admitted,
    30 and 10 decode steps, 120 and 14 tokens out with the prefill's."""
    stats = dict(STATS, decode_steps=40, admitted=8, tokens_out=134)
    run = run_of([], stats)
    assert run.cell.traffic["batch_size"] == 8
    assert harness.read_metric("decode_slot_use", run) == pytest.approx(
        100.0 * (134 - 8) / (40 * 8))


def test_nothing_is_read_from_an_engine_without_the_counters():
    """An engine that stamps no admission and counts no admitted requests
    gives neither metric, and raises nothing."""
    run = run_of([req() for _ in range(5)], dict(STATS, decode_steps=9,
                                                 tokens_out=14))
    assert harness.read_metric("queue_wait_p95_ms", run) is None
    assert harness.read_metric("decode_slot_use", run) is None
    # no decode step in the window
    run = run_of([], dict(STATS, admitted=3, tokens_out=3))
    assert harness.read_metric("decode_slot_use", run) is None


def test_a_whole_run_reads_both(cpu_devices):
    cell = tiny_cell()
    run, _, _ = harness.serve_window(cell, SEED, 1.0, cpu_devices[:1],
                                     time.perf_counter())
    waits = [s.req.admitted_at - s.req.submitted_at for s in run.served]
    assert len(waits) > 4 and min(waits) >= 0
    assert run.stats["admitted"] == len(run.served)
    assert run.stats["compiles"] == 0      # set-up compiled every shape
    q = harness.read_metric("queue_wait_p95_ms", run)
    assert q == pytest.approx(np.percentile(waits, 95) * 1e3)
    use = harness.read_metric("decode_slot_use", run)
    emitted = sum(len(s.req.out_tokens) - 1 for s in run.served)
    assert use == pytest.approx(
        100.0 * emitted / (run.stats["decode_steps"] * 8))
    assert 0 < use <= 100
