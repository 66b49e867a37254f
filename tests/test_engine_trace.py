"""ServeEngine's host spans (``serve.*``) and counters: the spans a
profiler session records with their args, the request clocks, the
admitted count behind decode slot use, and the step-compile counter."""
import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.models import build
from repro.serve import EngineConfig, ServeEngine
from repro.serve import engine as engine_mod

PROMPT = 8


@pytest.fixture(scope="module")
def model():
    cfg = reduce_for_smoke(get_config("qwen2_0_5b"))
    bundle = build(cfg)
    return bundle, bundle.init(jax.random.PRNGKey(0))


def make(model, batch_size):
    bundle, params = model
    return ServeEngine(bundle, params, EngineConfig(batch_size=batch_size,
                                                    max_seq=32))


def submit(eng, max_new, prompt_len=PROMPT):
    return [eng.submit(np.arange(prompt_len, dtype=np.int32) + i,
                       max_new_tokens=n) for i, n in enumerate(max_new)]


def serve_spans(path):
    """(start_ns, name, args) of every ``serve.*`` host event, in order."""
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.name, dict(e.stats))
                        for e in line.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[0])


def test_spans_name_each_piece_of_host_work(model, tmp_path):
    """Two batches (batch size 2; answers of 3, 5 and 2 tokens): one admit,
    prefill, read and emit each, then one dispatch / read / emit per decode
    step, every span carrying its batch's id."""
    eng = make(model, 2)
    eng.compile(PROMPT)
    reqs = submit(eng, [3, 5, 2])
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = serve_spans(path)
    names = {engine_mod.SPAN_ADMIT, engine_mod.SPAN_PREFILL,
             engine_mod.SPAN_DISPATCH, engine_mod.SPAN_READ,
             engine_mod.SPAN_EMIT}
    assert {n for _, n, _ in spans} == names

    def of(name, b):
        return [a for _, n, a in spans if n == name and a["batch"] == b]

    # the admit span names the batch's requests
    batch_of = {}
    for b in (0, 1):
        (admit,) = of(engine_mod.SPAN_ADMIT, b)
        rids = range(admit["rid0"], admit["rid1"] + 1)
        assert admit["n"] == len(rids)
        batch_of.update({rid: b for rid in rids})
        (prefill,) = of(engine_mod.SPAN_PREFILL, b)
        assert prefill["prompt_len"] == PROMPT
    assert batch_of == {0: 0, 1: 0, 2: 1}

    # batch 0 decodes to its longest answer (5): 4 steps; batch 1: 1 step
    steps = {0: 4, 1: 1}
    assert eng.stats["decode_steps"] == sum(steps.values())
    for b, n in steps.items():
        assert [a["pos"] for a in of(engine_mod.SPAN_DISPATCH, b)] == \
            list(range(PROMPT, PROMPT + n))
        assert len(of(engine_mod.SPAN_READ, b)) == n + 1
        assert len(of(engine_mod.SPAN_EMIT, b)) == n + 1
    # live slots per step, and tokens emitted per emit span
    assert [a["live"] for a in of(engine_mod.SPAN_DISPATCH, 0)] == \
        [2, 2, 1, 1]
    assert [a["n"] for a in of(engine_mod.SPAN_EMIT, 0)] == [2, 2, 2, 1, 1]
    assert sum(a["n"] for _, n, a in spans if n == engine_mod.SPAN_EMIT) \
        == eng.stats["tokens_out"] == sum(len(r.out_tokens) for r in reqs)

    # within a batch: admit, prefill, read, emit, then per step
    # dispatch, read, emit
    order = [n for _, n, a in spans if a["batch"] == 0]
    assert order == [engine_mod.SPAN_ADMIT, engine_mod.SPAN_PREFILL,
                     engine_mod.SPAN_READ, engine_mod.SPAN_EMIT] + \
        [engine_mod.SPAN_DISPATCH, engine_mod.SPAN_READ,
         engine_mod.SPAN_EMIT] * 4


def test_request_clocks_and_decode_slot_use(model):
    """One batch of 4 slots holding answers of 2, 5 and 3 tokens: 4 decode
    steps, of whose 16 slot-steps 7 emit a token."""
    eng = make(model, 4)
    reqs = submit(eng, [2, 5, 3])
    eng.run()
    assert all(r.submitted_at <= r.admitted_at for r in reqs)
    assert len({r.admitted_at for r in reqs}) == 1
    st = eng.stats
    assert st["admitted"] == 3 and st["decode_steps"] == 4
    assert st["tokens_out"] == 10
    used = (st["tokens_out"] - st["admitted"]) / (st["decode_steps"] * 4)
    assert used == sum(len(r.out_tokens) - 1 for r in reqs) / 16 == 7 / 16

    # a later batch is admitted after it is submitted, and counted
    (late,) = submit(eng, [2])
    eng.run()
    assert reqs[0].admitted_at < late.submitted_at <= late.admitted_at
    assert eng.stats["admitted"] == 4


def test_compiles_count_new_shapes_only(model):
    eng = make(model, 2)
    assert eng.stats["compiles"] == 0
    eng.compile(PROMPT)
    assert eng.stats["compiles"] == 2
    submit(eng, [3, 3])
    eng.run()
    assert eng.stats["compiles"] == 2          # warm: nothing compiles
    # a new prompt length compiles prefill; decode's shapes are unchanged
    submit(eng, [3, 3], prompt_len=PROMPT + 4)
    eng.run()
    assert eng.stats["compiles"] == 3
    submit(eng, [3, 3], prompt_len=PROMPT + 4)
    eng.run()
    assert eng.stats["compiles"] == 3
