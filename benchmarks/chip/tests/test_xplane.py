"""Trace reduction (``benchmarks/chip/xplane.py``) on hand-built events."""
import pytest

from benchmarks.chip import xplane
from benchmarks.chip.xplane import Trace

# Device 0: two fusions that overlap, an all-to-all partly under a
# fusion, a gap, and a module run that holds them.
OPS = [("fusion.1", 0.0, 2.0), ("fusion.2", 1.0, 3.0),
       ("all-to-all.3", 2.5, 4.0), ("fusion.4", 6.0, 7.0)]
SPANS = [("trace.window", 0.0, 10.0), ("ep.step", 0.0, 5.0),
         ("ep.wait", 3.0, 5.0), ("token_fetch", 5.0, 10.0)]


def test_busy_is_the_union():
    assert xplane.busy_s(OPS, 0.0, 10.0) == pytest.approx(5.0)
    assert xplane.busy_s(OPS, 1.5, 6.5) == pytest.approx(3.0)


def test_idle_gaps():
    assert xplane.idle_gaps(OPS, 0.0, 10.0) == [(4.0, 6.0), (7.0, 10.0)]


def test_exposed_collective():
    # all-to-all runs 2.5-4.0; fusion.2 covers it until 3.0.
    assert xplane.exposed_collective_s(OPS, 0.0, 10.0) == pytest.approx(1.0)
    assert xplane.exposed_collective_s(OPS, 3.5, 10.0) == pytest.approx(0.5)


def test_gaps_named_by_innermost_span():
    tr = Trace(ops={0: OPS}, spans=SPANS)
    got = dict((n, v) for n, v in xplane.named_gaps(tr, 0, 0.0, 10.0))
    # gap 4-6 has its midpoint (5.0) in ep.wait (shortest holder), gap
    # 7-10 in token_fetch.
    assert got == {"ep.wait": pytest.approx(2.0),
                   "token_fetch": pytest.approx(3.0)}


def test_top_ops_and_module_runs():
    assert xplane.top_ops(OPS, 0.0, 10.0)[0] == ["fusion.1", 2.0]
    tr = Trace(modules={0: [("jit_step(12)", 0.0, 4.0), ("jit_stepper", 5.0, 6.0),
                            ("jit_step", 6.0, 7.5)]})
    assert xplane.module_runs(tr, 0, "jit_step", 0.0, 10.0) == [4.0, 1.5]


def test_window_span_required():
    with pytest.raises(ValueError):
        Trace(spans=[("ep.step", 0, 1)]).window()
    assert Trace(spans=SPANS).window() == (0.0, 10.0)


def test_container_ops_dropped_and_names_shortened():
    evs = [("while", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 2.0, 5.0),
           ("c", 11.0, 12.0)]
    assert [n for n, _, _ in xplane.leaves(evs)] == ["a", "b", "c"]
    text = ("%fusion.202 = bf16[64,32,8,512]{3,0,2,1:T(8,128)(2,1)} "
            "fusion(bf16[64,32,8,1024]{3,2,1,0} %fusion.200), kind=kOutput")
    assert xplane.short_name(text) == "%fusion.202 fusion bf16[64,32,8,512]"
    a2a = "%all-to-all.3 = bf16[4,640,4096]{2,1,0} all-to-all(bf16[4] %x)"
    assert xplane.is_collective(xplane.short_name(a2a))
    assert xplane.short_name("round.prefill") == "round.prefill"
