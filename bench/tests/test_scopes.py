"""The per-layer reduction: scope paths from HLO metadata, device self time
per named scope inside the round step's own module, device idle inside the
data plane's spans, and the bytes the program handed the device."""
import os
from types import SimpleNamespace

import pytest

from bench import scopes as sc
from bench import spec
from bench.scopes import HostSpan, ScopeTrace
from bench.trace_reduce import WINDOW, Span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tpu_v5e_scopes.xplane.pb")
RECORDED_HLO = os.path.join(DATA, "tpu_v5e_scopes.hlo.txt")

HLO = """HloModule jit_round_step, is_scheduled=true, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(round_step)/while/body/closed_call/local_step/while/body/local_apply/mul" stack_frame_id=4}
}

%fused_computation.5 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(f32[8]{0} %param_0.1), metadata={op_name="jit(round_step)/while/body/closed_call/local_step/while/body/jit(_where)/neg"}
  ROOT %add.2 = f32[8]{0} add(f32[8]{0} %neg.1, f32[8]{0} %param_0.1), metadata={op_name="jit(round_step)/while/body/closed_call/local_step/while/body/jvp(blocks)/add"}
}

%body.4 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.412 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_step)/while/body/closed_call/local_step/while/body/closed_call/transpose(jvp(lm_head))/dot_general" stack_frame_id=7}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %fusion.412), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_step)/while/body/closed_call/local_step/while/body/local_apply/mul"}
  %fusion.490 = f32[8]{0} fusion(f32[8]{0} %fusion.3), kind=kLoop, calls=%fused_computation.5
  ROOT %copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.490)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="p"}
  %while.2 = f32[8]{0} while(f32[8]{0} %p), condition=%cond, body=%body.4, metadata={op_name="jit(round_step)/while"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %while.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(round_step)/while/body/accumulate/add"}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.7)
  ROOT %fusion.9 = f32[8]{0} fusion(f32[8]{0} %copy.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(round_step)/server_update/mul"}
}
"""


def op(start, end, name):
    return Span(start, end, f"%{name} = f32[8]{{0}} {name.split('.')[0]}(...)")


def test_scope_map_reads_every_scope_on_the_path():
    m = sc.scope_map(HLO)
    assert sc.module_name(HLO) == "jit_round_step"
    assert m["mul.3"] == {"local_step", "local_apply"}
    assert m["fusion.412"] == {"local_step", "lm_head"}
    assert m["fusion.7"] == {"accumulate"}
    assert m["while.2"] == set() and m["p"] == set()
    # without metadata: a fusion takes what its fused computation shares, any
    # other instruction what its own computation shares
    assert m["fusion.490"] == {"local_step"}
    assert m["copy.2"] == {"local_step"}
    assert m["copy.1"] == set()
    assert sc.instruction("%fusion.412 = bf16[2,512]{1,0} fusion(...)") == "fusion.412"


def synthetic():
    """Window 0-10 s.  Round step runs at 1-5 and 6-9; another module at
    5.2-5.8 whose instruction names repeat the step's.  Data spans 0-1.5
    and 5-5.9; the device idles 0-1, 5-5.2, 5.8-6, 9-10."""
    ops = [op(1.0, 3.0, "while.2"), op(1.0, 2.0, "fusion.412"), op(2.0, 2.6, "fusion.3"),
           op(2.6, 2.8, "fusion.490"), op(2.8, 3.0, "copy.2"),
           op(3.0, 3.5, "fusion.7"), op(3.5, 4.0, "copy.1"), op(4.0, 5.0, "fusion.9"),
           op(5.2, 5.8, "fusion.412"),                      # the other module
           op(6.0, 8.0, "while.2"), op(6.0, 8.0, "fusion.412"), op(8.0, 9.0, "fusion.7")]
    modules = [Span(1.0, 5.0, "jit_round_step(123)"), Span(5.2, 5.8, "jit_other(9)"),
               Span(6.0, 9.0, "jit_round_step(123)")]
    data = [HostSpan(0.0, 1.0, "data/materialize", 0), HostSpan(1.0, 1.5, "data/to_device", 100),
            HostSpan(5.0, 5.9, "data/to_device", 100),
            HostSpan(10.5, 11.0, "data/to_device", 100)]    # after the window
    return ScopeTrace([ops], [modules], Span(0.0, 10.0, WINDOW), data)


def test_reduce_counts_self_time_per_scope_inside_the_module():
    red = sc.reduce(synthetic(), HLO)
    assert red["scopes"] == {"local_step": pytest.approx(4.0), "local_apply": pytest.approx(0.6),
                             "lm_head": pytest.approx(3.0), "accumulate": pytest.approx(1.5),
                             "server_update": pytest.approx(1.0)}
    # copy.1 names no scope; the while loops' bodies fill them (self time 0)
    assert red["other_s"] == pytest.approx(0.5)
    assert red["round_step_s"] == pytest.approx(7.0)
    # idle 0-1 and 5-5.2 and 5.8-6 lie inside data spans (1.0 + 0.2 + 0.1)
    assert red["data_wait_s"] == pytest.approx(1.3)
    assert red["data_wait_by_span"] == {"data/materialize": pytest.approx(1.0),
                                        "data/to_device": pytest.approx(0.3)}
    assert red["data_span_s"] == {"data/materialize": pytest.approx(1.0),
                                  "data/to_device": pytest.approx(1.4)}
    assert red["h2d_bytes"] == 200


def test_per_layer_metrics_and_a_program_without_scopes():
    red = sc.reduce(synthetic(), HLO)
    m = sc.per_layer(red, rounds=2, computed_steps=8)
    assert m == {"local_step_ms": pytest.approx(500.0), "lm_head_ms": pytest.approx(375.0),
                 "local_apply_ms": pytest.approx(75.0), "accumulate_ms": pytest.approx(750.0),
                 "server_update_ms": pytest.approx(500.0), "data_wait_ms": pytest.approx(650.0),
                 "h2d_bytes": 100.0}
    # the parent commit's program: no scopes in its HLO, no data/* spans
    bare = HLO.replace("local_step/", "").replace("local_apply/", "").replace(
        "(lm_head)", "()").replace("accumulate/", "").replace("server_update/", "")
    t = synthetic()
    red = sc.reduce(ScopeTrace(t.ops, t.modules, t.window, []), bare)
    assert red["scopes"] == {} and red["other_s"] == pytest.approx(7.0)
    assert sc.per_layer(red, rounds=2, computed_steps=8) == {}


def test_per_step_metrics_divide_by_computed_steps():
    """The same device time over fewer computed steps (a sequential cohort
    computes each client only to its last unmasked step) reads more per
    step; the per-round metrics do not move."""
    red = sc.reduce(synthetic(), HLO)
    full, trimmed = (sc.per_layer(red, rounds=2, computed_steps=n) for n in (8, 2))
    for m in ("local_step_ms", "lm_head_ms", "local_apply_ms"):
        assert trimmed[m] == pytest.approx(4 * full[m])
    for m in ("accumulate_ms", "server_update_ms", "data_wait_ms", "h2d_bytes"):
        assert trimmed[m] == full[m]


SEVEN = ("local_step_ms", "lm_head_ms", "local_apply_ms", "accumulate_ms",
         "server_update_ms", "data_wait_ms", "h2d_bytes")


def test_readers_of_a_run_record():
    red = sc.reduce(synthetic(), HLO)
    run = SimpleNamespace(scopes=red, rounds=[None, None], computed_steps=8)
    got = {m: spec.metric_reader(m)(run) for m in SEVEN}
    assert got == sc.per_layer(red, rounds=2, computed_steps=8)
    assert all(spec.metric_reader(m)(SimpleNamespace(scopes=None)) is None for m in SEVEN)


def test_no_window_or_no_device_ops_is_an_error():
    t = synthetic()
    with pytest.raises(ValueError):
        sc.reduce(ScopeTrace(t.ops, t.modules, None, t.data), HLO)
    with pytest.raises(ValueError):
        sc.reduce(ScopeTrace([[]], t.modules, t.window, t.data), HLO)


def test_recorded_chip_trace():
    """Recorded on a TPU v5e by ``record_scopes_trace.py``: three rounds of
    a 4-step scan, each after host pauses in ``data/index_plan`` and
    ``data/materialize`` and a 4 MiB ``data/to_device``, then another
    module.  The values below are the file's event durations, summed by
    hand from its listing."""
    with open(RECORDED_HLO) as f:
        red = sc.reduce(sc.load(RECORDED), f.read())
    assert red["module"] == "jit_round_step"
    # the compiler fused the descent into the gradient's fusion (named for
    # lm_head) and delta, accumulate and server step into one fusion (named
    # for its root, server_update), so three scopes hold device time
    assert set(red["scopes"]) == {"local_step", "lm_head", "server_update"}
    # the three while loops with all they hold: 27.683 + 27.820 + 27.726 us
    assert red["scopes"]["local_step"] == pytest.approx(83.229e-6, rel=1e-9)
    # 12 multiply_reduce_fusion.2 + 12 subtract_convert_fusion.2
    assert red["scopes"]["lm_head"] == pytest.approx(76.986e-6, rel=1e-9)
    # add_convert_fusion: 3.994 + 4.127 + 4.106 us
    assert red["scopes"]["server_update"] == pytest.approx(12.227e-6, rel=1e-9)
    # the step modules' self time; the other module's three ops (about
    # 4.8 us each) are not in it.  Other: the input's convert, the async
    # weight slices, the loss's reduce.
    assert red["round_step_s"] == pytest.approx(122.640e-6, rel=1e-9)
    assert red["other_s"] == pytest.approx(27.184e-6, rel=1e-9)
    # the data spans last 51.124128 ms; the device's clock runs ahead of the
    # host's, so the steps of rounds 1 and 2 start inside their to_device
    # spans and 81.949 us of those are busy
    assert red["data_wait_s"] == pytest.approx(51.042179e-3, rel=1e-9)
    assert red["data_wait_by_span"]["data/materialize"] == pytest.approx(
        (10.952560 + 10.877410 + 10.860499) * 1e-3, rel=1e-6)
    assert red["h2d_bytes"] == 3 * 4 * 256 * 1024 * 4
    m = sc.per_layer(red, rounds=3, computed_steps=12)
    assert m["h2d_bytes"] == 4194304 and "local_apply_ms" not in m
    # the readers of the result line: the values above, 12 computed steps
    run = SimpleNamespace(scopes=red, rounds=[None] * 3, computed_steps=12)
    got = {name: spec.metric_reader(name)(run) for name in SEVEN}
    assert got == {
        "local_step_ms": pytest.approx(83.229e-3 / 12, rel=1e-9),
        "lm_head_ms": pytest.approx(76.986e-3 / 12, rel=1e-9),
        "local_apply_ms": None, "accumulate_ms": None,
        "server_update_ms": pytest.approx(12.227e-3 / 3, rel=1e-9),
        "data_wait_ms": pytest.approx(51.042179 / 3, rel=1e-9),
        "h2d_bytes": 4194304.0}
