"""The trace reducer on a hand-made trace and on a small trace recorded
on a TPU v5e (`data/sample.xplane.pb`: three `bench.fit` spans of five
small programs each inside `bench.window`, host sleeps between)."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import trace_reduce  # noqa: E402

SAMPLE = Path(__file__).resolve().parent / "data" / "sample.xplane.pb"


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def hand_trace():
    host = plane("/host:CPU", python=[
        ev("bench.window", 1000, 2000), ev("bench.fit", 1000, 1500),
        ev("bench.fit", 1600, 2000), ev("other", 0, 3000)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 900, 1100),
                         ev("scatter.2", 1050, 1200),
                         ev("fusion.1", 1700, 1800), ev("copy", 2500, 2600)],
                XLA_Modules=[ev("jit_step(7)", 900, 1250),
                             ev("jit_step(8)", 1700, 1800)])
    other = plane("/device:TPU:1", XLA_Ops=[ev("x", 1000, 2000)])
    return [host, dev, other]


def test_busy_idle_and_gaps_by_hand():
    s = trace_reduce.reduce_planes(hand_trace(), device_ids=[0])
    assert s["window_s"] == pytest.approx(1000e-9)
    # ops clipped to the window: [1000, 1200] and [1700, 1800]
    assert s["busy_s"] == pytest.approx(300e-9)
    assert s["modules"]["jit_step"]["count"] == 2
    assert s["modules"]["jit_step"]["seconds"] == pytest.approx(350e-9)
    assert s["ops"]["jit_step:fusion.1"] == pytest.approx(200e-9)
    # gaps: [1200, 1700] mid 1450 in a fit; [1800, 2000] mid 1900 in a fit
    assert s["idle_gaps"] == {"bench.fit": pytest.approx(700e-9)}


def test_gap_outside_every_span_and_two_chips():
    planes = hand_trace()
    planes[0].lines[0].events[1] = ev("bench.fit", 1000, 1300)
    s = trace_reduce.reduce_planes(planes, device_ids=[0, 1])
    assert s["busy_s"] == pytest.approx((300e-9 + 1000e-9) / 2)
    assert s["idle_gaps"]["bench.fit"] == pytest.approx(200e-9)
    assert s["idle_gaps"]["no bench span"] == pytest.approx(500e-9)
    b = trace_reduce.breakdown(s, top=1)
    assert b["idle_gaps"] == [["no bench span", pytest.approx(500e-9)]]
    assert len(b["device_ops"]) == 1


def test_no_window_span_is_an_error():
    planes = hand_trace()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes)


def test_recorded_trace():
    """15 executions of one program, 5 in each of 3 `bench.fit` spans;
    the host sleeps inside and between the spans.  The device clock sits
    about a millisecond from the host's in this trace, so the window's
    first execution (0.5 ms) falls before the host span opens."""
    s = trace_reduce.reduce(SAMPLE)
    assert s["modules"]["jit_step"]["count"] == 14
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["busy_s"] == pytest.approx(
        sum(s["ops"].values()), rel=0.01)          # no overlapping ops
    assert s["modules"]["jit_step"]["seconds"] >= s["busy_s"]
    assert all(k.startswith("jit_step:") for k in s["ops"])
    idle = s["window_s"] - s["busy_s"]
    assert sum(s["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
    # the sleeps inside the fits are the longest gaps
    assert max(s["idle_gaps"], key=s["idle_gaps"].get) == "bench.fit"
    b = trace_reduce.breakdown(s)
    assert b["device_ops"][0][0] == "jit_step:fusion"
