"""End-to-end and per-layer arithmetic on a synthetic stamped timeline."""
import types

import numpy as np
import pytest

import harness
import work
from conftest import tiny_cell

T0, WINDOW = 100.0, 10.0
STEP = 0.01


def served(due, first, n, step=STEP, stall_at=None, stall=0.0, prompt=16):
    out = harness.StampList()
    for i in range(n):
        out.append(i)
    t = [first + i * step for i in range(n)]
    if stall_at is not None:
        t = [x + (stall if x >= stall_at else 0.0) for x in t]
    out.stamps[:] = t
    req = types.SimpleNamespace(out_tokens=out, done=True, rid=0)
    return harness.Served(req, due, due, n, np.zeros(prompt, np.int32))


def timeline(stall=0.0):
    """Ten requests due a second apart, each served 0.1 s after it is due,
    20 tokens 10 ms apart; with ``stall`` every stamp from t=104.15, in the
    middle of request 4's answer, comes later by that much."""
    return [served(T0 + k, T0 + k + 0.1, 20, stall_at=T0 + 4.15,
                   stall=stall) for k in range(10)]


def run_of(served_list, stats=None, trace=None):
    cell = tiny_cell()
    return harness.Run(cell=cell, shape=work.Shape.of(cell.model),
                       setup_s=12.5, t0=T0, window_s=WINDOW,
                       served=served_list,
                       stats=stats or {"decode_steps": 0, "prefill_s": 0.0,
                                       "decode_s": 0.0},
                       device_kind="TPU v5 lite", trace=trace)


def metric(name, run):
    return harness.read_metric(name, run)


def test_steady_timeline():
    run = run_of(timeline())
    assert metric("ttft_p95_ms", run) == pytest.approx(100.0)
    assert metric("tpot_p95_ms", run) == pytest.approx(10.0)
    assert metric("setup_s", run) == 12.5


def test_a_stall_inside_the_window_moves_every_metric():
    run = run_of(timeline(stall=2.0))
    # request 4 stalls mid-answer: its 19 gaps hold 2 s more
    assert metric("tpot_p95_ms", run) > 50.0
    # every request due after the stall began gets its first token 2 s late
    ttft = sorted((s.stamps[0] - s.due) * 1e3 for s in run.served)
    assert ttft[-5:] == pytest.approx([2100.0] * 5)
    assert metric("ttft_p95_ms", run) == pytest.approx(2100.0)


def test_shares_of_the_peaks():
    stats = {"decode_steps": 100, "prefill_s": 0.5, "decode_s": 1.0,
             "prefills": 5}
    run = run_of(timeline(), stats=stats)
    s = run.shape
    flops = 10 * s.prefill_flops(16)
    assert metric("mfu.prefill", run) == pytest.approx(
        100 * flops / (0.5 * 197e12))
    dec = 10 * sum(s.decode_flops(16 + i - 1) for i in range(1, 20))
    assert metric("mfu.decode", run) == pytest.approx(
        100 * dec / (1.0 * 197e12))
    need = 100 * s.weight_bytes() + 10 * sum(
        s.decode_slot_bytes(16 + i - 1) for i in range(1, 20))
    assert metric("hbm_roofline.decode", run) == pytest.approx(
        100 * need / (1.0 * 819e9))


def test_trace_metrics_are_left_out_without_a_trace():
    run = run_of(timeline())
    assert metric("device_idle_share.serving", run) is None


def test_a_token_without_a_stamp_or_a_short_answer_is_a_fault():
    ok = served(T0, T0 + 0.1, 5)
    assert ok.fault() is None
    short = served(T0, T0 + 0.1, 5)
    short.n_asked = 6
    assert "5 of 6" in short.fault()
    bare = served(T0, T0 + 0.1, 5)
    bare.req.out_tokens = list(bare.req.out_tokens)
    assert "stamp" in bare.fault()
