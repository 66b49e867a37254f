"""Traffic draws: deterministic per seed, inside the stated ranges, and
the same amount of work for every seed."""
import numpy as np
import pytest

import traffic
from conftest import BENCH

CELLS = ["qwen2_0_5b-chat-open"]
BIG_SEED = 2**31 + 12_345


@pytest.mark.parametrize("name", CELLS)
def test_draws_are_deterministic_and_in_range(name):
    spec = traffic.load(BENCH / "traffic" / f"{name}.json")
    a = traffic.draws(spec, 151_936, BIG_SEED, 30.0)
    b = traffic.draws(spec, 151_936, BIG_SEED, 30.0)
    c = traffic.draws(spec, 151_936, BIG_SEED + 1, 30.0)
    assert [d.n_out for d in a] == [d.n_out for d in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    o = spec["output"]
    lens = np.array([d.n_out for d in a])
    assert lens.min() >= o["min"] and lens.max() <= o["max"]
    assert abs(np.median(lens) - o["median"]) <= 0.1 * o["median"]
    assert all(len(d.prompt) == spec["prompt_tokens"] for d in a)
    assert all(0 <= d.prompt.min() and d.prompt.max() < 151_936 for d in a)
    assert all(spec["prompt_tokens"] + d.n_out - 1 <= spec["max_seq"]
               for d in a)
    due = np.array([d.due_s for d in a])
    assert due[0] == 0 and np.all(np.diff(due) >= 0) and due[-1] < 30
    rate = len(due) / 30.0
    assert abs(rate - spec["rate_per_s"]) <= 0.05 * spec["rate_per_s"]


def test_open_loop_work_does_not_depend_on_the_seed():
    spec = traffic.load(BENCH / "traffic" / "qwen2_0_5b-chat-open.json")
    runs = [traffic.draws(spec, 1000, s, 30.0) for s in (1, 2, BIG_SEED)]
    counts = {len(r) for r in runs}
    totals = [sum(d.n_out for d in r) for r in runs]
    assert len(counts) == 1
    assert max(totals) - min(totals) <= 0.01 * min(totals)
    gaps = [np.sort(np.diff([d.due_s for d in r])) for r in runs]
    assert np.allclose(gaps[0][:-2], gaps[1][:-2], atol=0.2)


def test_any_sixteen_consecutive_requests_mix_short_and_long():
    spec = traffic.load(BENCH / "traffic" / "qwen2_0_5b-chat-open.json")
    lens = np.array([d.n_out for d in traffic.draws(spec, 1000, 7, 30.0)])
    windows = np.lib.stride_tricks.sliding_window_view(lens, 16)
    assert windows.min(1).max() <= 70 and windows.max(1).min() >= 250


def test_a_prompt_that_overruns_max_seq_is_refused(tmp_path):
    p = tmp_path / "t.json"
    p.write_text('{"loop": "open", "rate_per_s": 1, "prompt_tokens": 10,'
                 ' "output": {"median": 4, "sigma": 0.5, "min": 1,'
                 ' "max": 8}, "batch_size": 2, "max_seq": 16,'
                 ' "check_requests": 1}')
    with pytest.raises(ValueError, match="max_seq"):
        traffic.load(p)
