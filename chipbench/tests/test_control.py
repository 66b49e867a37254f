"""The control at a size a test run holds: the program's readings and the
fp8 control's, read as ``control.py`` reads them on the chip, and the
control's widest gap, put where a run puts the program's, fails the
configuration's limit."""
import control
import harness
from conftest import tiny_cell

SEEDS = [1, 2**31 + 3]


def test_the_control_fails_the_limit_and_the_program_does_not(cpu_devices):
    cell = tiny_cell()
    out = control.readings(cell, SEEDS, 1.0, cpu_devices[:1])
    limit = cell.model["check"]["max_logit_gap"]
    assert out["seeds"] == len(SEEDS)
    assert harness.judge({"max_logit_gap": {"value": out["lower"],
                                            "limit": limit}})
    assert not harness.judge({"max_logit_gap": {"value": out["upper"],
                                                "limit": limit}})
