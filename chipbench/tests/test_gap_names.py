"""The result line's idle gaps are named by the engine's ``serve.*`` spans,
on the trace recorded with them (``chat_1chip_serve.xplane.pb.gz``), and
the device numbers and the gaps are those the reduction gave before it
named them so."""
import xplane
from conftest import BENCH

SERVE = BENCH / "tests" / "data" / "chat_1chip_serve.xplane.pb.gz"
# the ten longest gaps as reduced before, each then named ``engine_run``
BEFORE_S = [0.0026340730000000002, 0.002459504, 0.00231671, 0.002298085,
            0.00215183, 0.002056175, 0.002047052, 0.002006728,
            0.0020060900000000003, 0.0019323300000000001]


def test_the_longest_gaps_are_named_by_engine_spans():
    r = xplane.reduce(SERVE)
    assert r.busy_s == 1.824427044
    assert r.idle_share_serving == 0.023292354154276884
    assert r.window_s == 1.8679356630000001
    assert [s for _, s in r.idle_gaps] == BEFORE_S
    # the cut holds one prefill; every other long gap waits on a token
    assert [label for label, _ in r.idle_gaps] == \
        ["serve.prefill"] + ["serve.read"] * 9
