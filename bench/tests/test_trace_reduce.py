"""The trace reduction: busy time as a union of device intervals, the window
from the harness's annotation, gaps named by the host span open in them."""
import os

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Span, Trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_v5e_small.xplane.pb")


def synthetic():
    host = [Span(0.0, 10.0, tr.WINDOW),
            Span(0.0, 2.0, "bench/round_batch"), Span(2.0, 6.0, "bench/dispatch"),
            Span(6.0, 7.0, "bench/metrics_fetch"), Span(7.0, 10.0, "bench/round_batch")]
    # overlapping ops on one device, one op hanging over the window's end
    ops = [Span(2.5, 4.0, "fusion.1"), Span(4.0, 4.8, "fusion.2"),
           Span(5.5, 6.0, "fusion.1"), Span(9.5, 11.0, "copy.3")]
    return Trace([ops], host)


def test_union_and_idle():
    red = tr.reduce(synthetic())
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.3 + 0.5 + 0.5)
    assert red["device_ops"] == [["fusion.1", pytest.approx(2.0)],
                                 ["fusion.2", pytest.approx(0.8)],
                                 ["copy.3", pytest.approx(0.5)]]
    # gaps 0-2.5 (round_batch), 4.8-5.5 (dispatch), 6-9.5 (round_batch)
    assert red["idle_gaps"] == [["round_batch", pytest.approx(3.5)],
                                ["round_batch", pytest.approx(2.5)],
                                ["dispatch", pytest.approx(0.7)]]


def test_gap_outside_any_span_is_untracked():
    t = synthetic()
    host = [s for s in t.host if s.start != 7.0]
    red = tr.reduce(Trace(t.devices, host))
    assert red["idle_gaps"][0] == ["untracked", pytest.approx(3.5)]


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(Trace([], synthetic().host))


def test_nested_ops_count_their_self_time():
    ops = [Span(0.0, 10.0, "%while.1 = (s32[]) while(...)"),
           Span(1.0, 3.0, "%fusion.2 = f32[8,128]{1,0} fusion(...)"),
           Span(4.0, 5.0, "%fusion.2 = f32[8,128]{1,0} fusion(...)")]
    red = tr.reduce(Trace([ops], [Span(0.0, 10.0, tr.WINDOW)]))
    assert red["busy_s"] == pytest.approx(10.0)
    assert red["device_ops"] == [["while.1 s32[]", pytest.approx(7.0)],
                                 ["fusion.2 f32[8,128]", pytest.approx(3.0)]]


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: three rounds of
    a 20 ms host pause in round_batch, two bf16 2048^3 products, and a fetch
    whose first call compiles a slice.  The device's clock there runs about
    1 ms ahead of the host's, so gaps are named at their middle."""
    red = tr.reduce(tr.load(RECORDED))
    assert red["window_s"] == pytest.approx(0.1604, abs=1e-3)
    assert 0.0 < red["busy_s"] < 0.005
    names = [n for n, _ in red["device_ops"]]
    assert names[:2] == ["fusion bf16[2048,2048]", "convolution_tanh_fusion bf16[2048,2048]"]
    gaps = red["idle_gaps"]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # the first fetch compiles (and the second round's pause falls in that
    # gap); the third round's pause is a gap of its own
    assert [g[0] for g in gaps[:4]] == ["metrics_fetch", "metrics_fetch",
                                        "round_batch", "round_batch"]
    assert 0.019 < gaps[2][1] < 0.023 and 0.019 < gaps[3][1] < 0.023
    assert sum(g[1] for g in red["idle_gaps"]) <= red["window_s"] - red["busy_s"] + 1e-9
