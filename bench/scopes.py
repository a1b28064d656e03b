"""Per-layer reduction of a traced window: device time per named scope of
the round step, and the data plane's program spans.

The program names its layers twice over.  Inside the jitted round step,
``jax.named_scope`` puts each layer's name on its ops' ``op_name`` metadata
(``.../local_step/.../transpose(jvp(lm_head))/dot_general``); the compiled
step's HLO text maps every instruction to that path.  On the host,
``repro.obs.trace.span`` enters a ``jax.profiler.TraceAnnotation`` while a
profile records, so the ``data/*`` spans sit on the host plane with their
``round`` and ``bytes`` stats, on the device trace's clock.

``reduce`` takes the profile of the harness's window (``bench/window``) and
the round step's compiled HLO text and gives

* ``scopes``: seconds of device self time per named scope, each op counted
  toward every scope on its path (``local_step`` holds ``lm_head``);
  only ops inside the round step's own module executions count, since
  instruction names repeat across modules;
* ``other_s``: the module's self time under no named scope (its costliest
  ops in ``other_ops``), and ``round_step_s`` the module's whole self time;
* ``data_wait_s``: device-idle time (the gaps of ``trace_reduce``) inside
  the union of the ``data/*`` spans, ``data_wait_by_span`` the same inside
  each span name's own union, and ``data_span_s`` that union's host time;
* ``h2d_bytes``: the ``bytes`` stats of the window's ``data/to_device``
  spans, summed.

``per_layer`` turns that into the per-layer metrics, which the readers
``bench/metrics/<name>.py`` take from a traced run's record (``read_metric``);
a traced ``bench.run`` also prints the whole reduction on a ``scopes:`` line.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import NamedTuple

from . import trace_reduce
from .trace_reduce import DEVICE_PLANE, OPS_LINE, WINDOW, Span

MODULES_LINE = "XLA Modules"
DATA_PREFIX = "data/"
TO_DEVICE = "data/to_device"
# the program's named scopes (src/repro: core/local.py, models/model.py,
# fed/rounds.py, fed/cohort/plane.py)
SCOPES = frozenset({
    "local_step", "local_apply", "client_delta", "embed", "blocks", "lm_head",
    "client_transform", "agg_coeffs", "accumulate", "server_update",
    "bank_gather", "bank_scatter", "downlink", "update_path", "plan_materialize",
})
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_NAME = re.compile(r"[\w.-]+")


class HostSpan(NamedTuple):
    start: float
    end: float
    name: str
    bytes: int     # the span's ``bytes`` stat, 0 without one


class ScopeTrace(NamedTuple):
    ops: list       # per device: list[Span] of its XLA ops
    modules: list   # per device: list[Span] of its XLA module executions
    window: Span | None
    data: list      # list[HostSpan] of the program's data/* spans


def module_name(hlo_text: str) -> str:
    """``jit_round_step`` from ``HloModule jit_round_step, ...``."""
    m = re.match(r"\s*HloModule ([^\s,]+)", hlo_text)
    if m is None:
        raise ValueError("not HLO text: no 'HloModule' header")
    return m.group(1)


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> the named scopes on its ``op_name`` path (a
    frozenset, empty when none).

    The compiler makes some instructions without metadata (fusions it
    formed or cloned, copies and converts it inserted).  Such a fusion takes
    the scopes that every instruction with metadata in its fused computation
    shares; any other such instruction, those that every instruction with
    metadata in its own computation shares (a copy inside the local step's
    loop body is the local step's)."""
    own: dict = {}          # instruction -> scopes, or None without metadata
    where: dict = {}        # instruction -> its computation
    calls: dict = {}        # fusion -> its fused computation
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        path = _OP_NAME.search(line)
        own[name] = (frozenset(SCOPES.intersection(_NAME.findall(path.group(1))))
                     if path else None)
        where[name] = comp
        f = _CALLS.search(line)
        if f:
            calls[name] = f.group(1)
    shared: dict = {}       # computation -> scopes its instructions share
    for name, sc in own.items():
        if sc is not None:
            c = where[name]
            shared[c] = shared[c] & sc if c in shared else sc
    return {name: sc if sc is not None
            else shared.get(calls.get(name), shared.get(where[name], frozenset()))
            for name, sc in own.items()}


def instruction(event_name: str) -> str:
    """``fusion.412`` from a device op's event name (its HLO text)."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def load(path: str) -> ScopeTrace:
    from jax.profiler import ProfileData

    def span(e):
        return Span(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)

    ops, modules, window, data = [], [], None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE) and plane.name[len(DEVICE_PLANE):].isdigit():
            lines = {line.name: [span(e) for e in line.events] for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            ops.append(lines.get(OPS_LINE, []))
            modules.append(lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = span(e)
                    elif e.name.startswith(DATA_PREFIX):
                        s = span(e)
                        data.append(HostSpan(s.start, s.end, e.name,
                                             int(dict(e.stats).get("bytes", 0))))
    return ScopeTrace(ops, modules, window, data)


def _inside(ops: list, intervals: list) -> list:
    """The ops that start inside one of ``intervals`` (disjoint)."""
    spans = sorted((m.start, m.end) for m in intervals)
    starts = [a for a, _ in spans]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < spans[i][1]:
            out.append(o)
    return out


def _overlap(xs: list, ys: list) -> float:
    """Total length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: ScopeTrace, hlo_text: str) -> dict:
    """Scope times of the module ``hlo_text`` compiles, and the data plane's
    spans, over the traced window (means over devices)."""
    if trace.window is None:
        raise ValueError(f"the trace holds no {WINDOW} annotation")
    if not any(trace.ops):
        raise ValueError("the trace holds no device operations")
    lo, hi = trace.window.start, trace.window.end
    module, scopes_of = module_name(hlo_text), scope_map(hlo_text)
    scopes: dict = defaultdict(float)
    other: dict = defaultdict(float)
    total = 0.0
    for ops, modules in zip(trace.ops, trace.modules):
        runs = [m for m in modules if m.name == module or m.name.startswith(module + "(")]
        for name, t in trace_reduce.self_times(_inside(ops, runs), lo, hi).items():
            total += t
            names = scopes_of.get(instruction(name), ())
            for scope in names:
                scopes[scope] += t
            if not names:
                other[trace_reduce.op_name(name)] += t
    n = len(trace.ops)
    busy = trace_reduce.union(trace.ops[0], lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]

    def wait(spans):
        return _overlap(idle, trace_reduce.union(spans, lo, hi))

    by_name: dict = defaultdict(list)
    for span in trace.data:
        by_name[span.name].append(span)

    return {
        "module": module,
        "scopes": {k: v / n for k, v in sorted(scopes.items())},
        "other_s": sum(other.values()) / n,
        "other_ops": sorted(([k, v / n] for k, v in other.items()),
                            key=lambda kv: kv[1], reverse=True)[:trace_reduce.TOP],
        "round_step_s": total / n,
        "data_wait_s": wait(trace.data),
        "data_wait_by_span": {name: wait(spans) for name, spans in by_name.items()},
        "data_span_s": {name: sum(b - a for a, b in trace_reduce.union(spans, lo, hi))
                        for name, spans in by_name.items()},
        "h2d_bytes": sum(s.bytes for s in trace.data
                         if s.name == TO_DEVICE and lo <= s.start < hi),
    }


def per_layer(red: dict, rounds: int, computed_steps: int) -> dict:
    """The per-layer metrics of a window of ``rounds`` rounds in which the
    program computed ``computed_steps`` local steps (``data/local_steps``
    ``computed``: each client up to its last unmasked step).  A metric whose
    scope the program does not name is left out."""
    sc = red["scopes"]
    out = {}
    for metric, scope in (("local_step_ms", "local_step"), ("lm_head_ms", "lm_head"),
                          ("local_apply_ms", "local_apply")):
        if scope in sc:
            out[metric] = 1e3 * sc[scope] / computed_steps
    if "accumulate" in sc:
        out["accumulate_ms"] = 1e3 * (sc["accumulate"] + sc.get("client_delta", 0.0)) / rounds
    if "server_update" in sc:
        out["server_update_ms"] = 1e3 * sc["server_update"] / rounds
    if red["h2d_bytes"]:
        out["data_wait_ms"] = 1e3 * red["data_wait_s"] / rounds
        out["h2d_bytes"] = red["h2d_bytes"] / rounds
    return out


def read_metric(run, name: str) -> float | None:
    """``per_layer``'s ``name`` for a run record (``bench/harness.py``
    ``RunRecord``); None without a scope reduction or without that scope."""
    if run.scopes is None:
        return None
    return per_layer(run.scopes, len(run.rounds), run.computed_steps).get(name)
