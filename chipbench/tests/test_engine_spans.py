"""Idle gaps named by the engine's spans (``engine_spans.py``), on two
traces recorded on a TPU v5e serving the chat cell: one from before the
engine had spans (``chat_1chip.xplane.pb.gz``), and one with them
(``chat_1chip_serve.xplane.pb.gz``: the traced ten seconds of a
``--trace 1`` run of the chat cell, cut to the 1.86 s from the third decode
step before a batch is formed through that batch's prefill and its first
18 decode steps, and gzipped)."""
import pytest

import engine_spans
import xplane
from conftest import BENCH

DATA = BENCH / "tests" / "data"
BEFORE = DATA / "chat_1chip.xplane.pb.gz"
SERVE = DATA / "chat_1chip_serve.xplane.pb.gz"
KINDS = {"serve.admit": {"batch", "n", "rid0", "rid1"},
         "serve.prefill": {"batch", "prompt_len"},
         "serve.dispatch": {"batch", "pos", "live"},
         "serve.read": {"batch"},
         "serve.emit": {"batch", "n"}}


@pytest.mark.parametrize("trace", [BEFORE, SERVE], ids=["before", "serve"])
def test_the_device_numbers_are_xplanes(trace):
    """Busy time, the idle share, the operations and every gap's length
    are reduced as ``xplane.reduce`` reduces them; a gap under a harness
    span keeps its name."""
    r, sums = engine_spans.reduce(trace)
    x = xplane.reduce(trace)
    assert (r.n_devices, r.window_s, r.busy_s, r.idle_share_serving,
            r.decode_steps, r.device_ops) == \
        (x.n_devices, x.window_s, x.busy_s, x.idle_share_serving,
         x.decode_steps, x.device_ops)
    assert [s for _, s in r.idle_gaps] == [s for _, s in x.idle_gaps]
    for (mine, _), (theirs, _) in zip(r.idle_gaps, x.idle_gaps):
        if theirs in xplane.LABEL_ORDER:
            assert mine == theirs
    devices, _ = xplane.load(trace)
    ops = devices[0].intervals()
    between = sum(b[0] - a[1] for a, b in zip(ops, ops[1:]))
    assert sum(v["idle_s"] for v in sums.values()) == \
        pytest.approx(between * 1e-9)


def test_without_engine_spans_the_gaps_are_named_as_before():
    r, sums = engine_spans.reduce(BEFORE)
    assert engine_spans.load(BEFORE) == []
    assert r == xplane.reduce(BEFORE)
    assert not any(label.startswith(engine_spans.PREFIX) for label in sums)


def test_every_engine_span_carries_its_args():
    spans = engine_spans.load(SERVE)
    assert {n for _, _, n, _ in spans} == set(KINDS)
    for _, _, name, args in spans:
        assert set(args) == KINDS[name], name
    # one batch is formed in the cut, and its decode steps follow it
    (admit,) = [a for _, _, n, a in spans if n == "serve.admit"]
    assert admit["n"] == admit["rid1"] - admit["rid0"] + 1
    (prefill,) = [a for _, _, n, a in spans if n == "serve.prefill"]
    assert prefill["batch"] == admit["batch"]
    steps = [a for _, _, n, a in spans
             if n == "serve.dispatch" and a["batch"] == admit["batch"]]
    assert [a["pos"] for a in steps] == list(
        range(prefill["prompt_len"], prefill["prompt_len"] + len(steps)))
    assert all(0 < a["live"] <= admit["n"] for a in steps)


def test_gaps_between_decode_steps_are_named_by_engine_spans():
    devices, harness = xplane.load(SERVE)
    dev = devices[0]
    gaps = engine_spans.label_gaps(dev, harness, engine_spans.load(SERVE))
    mods = sorted(dev.modules)

    def between_decode_steps(mid):
        before = [m for m in mods if m[1] <= mid]
        after = [m for m in mods if m[0] >= mid]
        return (before and after and "decode" in before[-1][2]
                and "decode" in after[0][2])

    decode_gaps = [(s, label) for s, mid, label in gaps
                   if between_decode_steps(mid)]
    assert len(decode_gaps) > 10
    assert all(label.startswith(engine_spans.PREFIX)
               or label in xplane.LABEL_ORDER for _, label in decode_gaps)
    # the longest gaps are those between decode steps, each named
    r, _ = engine_spans.reduce(SERVE)
    assert all(label != xplane.SERVING for label, _ in r.idle_gaps)


def test_a_harness_span_names_a_gap_before_an_engine_span():
    """Three gaps of 10, 20 and 30 us: the first under an engine span
    alone, the second under an engine span and the harness's ``submit``,
    the third under none."""
    ops = [(0, 10_000), (20_000, 30_000), (50_000, 60_000), (90_000, 99_000)]
    dev = xplane.Device([(s, e, "fusion.1", False) for s, e in ops],
                        [(0, 99_000, "jit__decode_step")])
    engine = [(5_000, 45_000, "serve.read", {"batch": 0})]
    harness = [(38_000, 42_000, "submit")]
    gaps = engine_spans.label_gaps(dev, harness, engine)
    assert [(round(s * 1e9), label) for s, _, label in gaps] == [
        (30_000, xplane.SERVING), (20_000, "submit"), (10_000, "serve.read")]
