"""The window's counters: a value the backend leaves as None does not make
a run raise, and the info line carries the window's compiles."""
import time

import harness
from conftest import tiny_cell

SEED = 2**31 + 23


def test_a_none_in_the_engines_stats_and_the_windows_compiles(
        cpu_devices, monkeypatch):
    from repro.serve.engine import ServeEngine
    compile_ = ServeEngine.compile

    def unreported(self, prompt_len):
        out = compile_(self, prompt_len)
        self.stats["decode_aliased_bytes"] = None
        return out

    monkeypatch.setattr(ServeEngine, "compile", unreported)
    res = harness.run_cell(tiny_cell(), SEED, 1.0, cpu_devices[:1],
                           time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["info"]["compiles"] == 0      # set-up compiled every shape


def test_only_numbers_are_subtracted():
    before = {"decode_steps": 3, "decode_s": 0.5,
              "decode_aliased_bytes": None}
    after = {"decode_steps": 10, "decode_s": 2.0,
             "decode_aliased_bytes": None}
    assert {k: harness._window_change(after[k], v)
            for k, v in before.items()} == {
        "decode_steps": 7, "decode_s": 1.5, "decode_aliased_bytes": None}
