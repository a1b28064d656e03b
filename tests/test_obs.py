"""Observability plane unit tests: sinks, instruments, histograms, tracer,
recompile sentinels, log levels, and the MetricLogger CSV union fix.
"""
import contextlib
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.obs import hist as obs_hist
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.utils.logging import (LOG_LEVELS, MetricLogger, debug, log,
                                 set_log_level, warn)


# ---------------------------------------------------------------------------
# Edge builders + in-jit histograms
# ---------------------------------------------------------------------------


def test_pow2_edges_shape_and_values():
    e = obs_hist.pow2_edges(6)
    assert e.shape == (7,)
    assert list(e[:-1]) == [0.0, 1.0, 2.0, 4.0, 8.0, 16.0]
    assert np.isinf(e[-1])
    with pytest.raises(ValueError):
        obs_hist.pow2_edges(1)


def test_log_edges_monotone():
    e = obs_hist.log_edges(1e-3, 1e3, 12)
    assert e.shape == (13,)
    assert np.all(np.diff(e) > 0)
    assert np.isclose(e[0], 1e-3) and np.isclose(e[-1], 1e3)
    with pytest.raises(ValueError):
        obs_hist.log_edges(1.0, 0.5, 4)


@settings(max_examples=30, deadline=None)
@given(vals=st.lists(st.floats(min_value=0.0, max_value=100.0),
                     min_size=1, max_size=64),
       bins=st.integers(min_value=2, max_value=12))
def test_fixed_histogram_matches_numpy(vals, bins):
    """In-jit counts == np.histogram on in-range data (right-open bins)."""
    edges = np.linspace(0.0, 100.0 + 1e-6, bins + 1)
    got = np.asarray(obs_hist.fixed_histogram(jnp.asarray(vals), edges))
    want, _ = np.histogram(np.asarray(vals, np.float32), bins=edges)
    assert got.sum() == len(vals)
    np.testing.assert_allclose(got, want)


def test_fixed_histogram_clamps_out_of_range():
    edges = np.asarray([0.0, 1.0, 2.0, 4.0])
    got = np.asarray(obs_hist.fixed_histogram(
        jnp.asarray([-5.0, 0.5, 3.0, 100.0]), edges))
    # -5 clamps into bin 0, 100 into the last bin — total count never drops
    np.testing.assert_allclose(got, [2.0, 0.0, 2.0])


def test_fixed_histogram_weights_drop_padding():
    edges = obs_hist.pow2_edges(4)
    vals = jnp.asarray([1.0, 2.0, 2.0, 7.0])
    w = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    got = np.asarray(obs_hist.fixed_histogram(vals, edges, weights=w))
    assert got.sum() == 3.0


def test_slot_sqnorms_and_tree_sqnorm_agree():
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((3,))}
    stacked = np.asarray(obs_hist.slot_sqnorms(tree))
    per_client = [float(obs_hist.tree_sqnorm(
        jax.tree.map(lambda x: x[i], tree))) for i in range(3)]
    np.testing.assert_allclose(stacked, per_client, rtol=1e-6)


def test_round_hist_edges_keys():
    from repro.configs.base import FLConfig

    fl = FLConfig(num_clients=4, cohort_size=2, telemetry_bins=8)
    base = obs_hist.round_hist_edges(fl, with_staleness=False, with_uplink=False)
    assert set(base) == {"hist_steps", "hist_update_norm"}
    allh = obs_hist.round_hist_edges(fl, with_staleness=True, with_uplink=True)
    assert set(allh) == {"hist_steps", "hist_update_norm", "hist_staleness",
                         "hist_uplink_mbytes"}
    assert all(e.shape == (9,) for e in allh.values())


# ---------------------------------------------------------------------------
# Sinks + registry
# ---------------------------------------------------------------------------


def test_sink_round_trip_memory_jsonl_csv(tmp_path):
    jl, cs = str(tmp_path / "m.jsonl"), str(tmp_path / "m.csv")
    reg = obs_metrics.MetricRegistry("t", sinks=[
        obs_metrics.InMemorySink(), obs_metrics.JSONLSink(jl),
        obs_metrics.CSVSink(cs)])
    reg.emit_row({"round": 0, "loss": 1.5})
    reg.emit_row({"round": 1, "loss": 1.25, "eval_acc": 0.5})
    reg.close()
    assert reg.sinks[0].records[1]["eval_acc"] == 0.5
    rows = [json.loads(line) for line in open(jl)]
    assert rows == reg.sinks[0].records
    lines = open(cs).read().strip().splitlines()
    # union of keys: the mid-run eval_acc column exists, first row's cell empty
    assert lines[0] == "round,loss,eval_acc"
    assert lines[1].endswith(",") and lines[2].endswith("0.5")


def test_build_sink_and_register(tmp_path):
    assert isinstance(obs_metrics.build_sink("memory"), obs_metrics.InMemorySink)
    s = obs_metrics.build_sink(f"jsonl:{tmp_path / 'x.jsonl'}")
    s.close()
    with pytest.raises(ValueError, match="unknown metric sink"):
        obs_metrics.build_sink("bogus")
    with pytest.raises(ValueError, match="overwrite=True"):
        obs_metrics.register_sink("memory", obs_metrics.InMemorySink)


def test_registry_instruments():
    reg = obs_metrics.MetricRegistry("t")
    reg.counter("n").inc()
    reg.counter("n").inc(2.0)
    reg.gauge("depth").set(3)
    h = reg.histogram("h", edges=[0.0, 1.0, 2.0])
    h.observe([0.5, 1.5, 1.7], weights=[1.0, 1.0, 2.0])
    h.merge_counts([1.0, 0.0])
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 3.0
    assert snap["gauges"]["depth"] == 3.0
    assert snap["histograms"]["h"]["counts"] == [2.0, 3.0]
    # get-or-create is type-strict; histogram first use needs edges
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("n")
    with pytest.raises(ValueError, match="must pass edges"):
        reg.histogram("h2")
    with pytest.raises(ValueError, match="merge of"):
        h.merge_counts([1.0, 2.0, 3.0])


def test_registry_dump_summary(tmp_path):
    reg = obs_metrics.MetricRegistry("t")
    reg.histogram("h", edges=obs_hist.pow2_edges(4)).observe([1.0, 2.0])
    p = str(tmp_path / "summary.json")
    reg.dump_summary(p)
    snap = json.load(open(p))
    assert snap["histograms"]["h"]["total"] == 2.0


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_is_noop_without_tracer():
    assert trace.active() is None
    s1, s2 = trace.span("x"), trace.span("y", a=1)
    assert s1 is s2  # the shared null span: zero allocation when off
    with s1:
        pass
    trace.counter("c", depth=1)  # no-op, no error


def test_tracer_spans_threads_and_chrome_export(tmp_path):
    with trace.capture(chrome=str(tmp_path / "t.json"),
                       jsonl=str(tmp_path / "t.jsonl")) as tr:
        with trace.span("round/step_dispatch", round=0):
            pass
        trace.counter("prefetch/queue_depth", depth=2)

        def worker():
            with trace.span("prefetch/plan_build", round=1):
                pass

        t = threading.Thread(target=worker, name="cohort-prefetch")
        t.start()
        t.join()
    assert trace.active() is None
    assert len(tr) == 3
    doc = json.load(open(tmp_path / "t.json"))
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"round/step_dispatch",
                                      "prefetch/plan_build"}
    assert all("dur" in e and "ts" in e for e in xs)
    threads = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert "cohort-prefetch" in threads
    # the two spans ran on different threads -> different (small) tids
    tids = {e["tid"] for e in xs}
    assert len(tids) == 2 and all(t < 16 for t in tids)
    lines = [json.loads(line) for line in open(tmp_path / "t.jsonl")]
    assert len(lines) == 3 and lines[0]["thread"]


def test_capture_is_reentrant():
    with trace.capture() as outer:
        with trace.span("outer"):
            pass
        with trace.capture() as inner:
            with trace.span("inner"):
                pass
        assert trace.active() is outer
        with trace.span("outer2"):
            pass
    assert len(inner) == 1 and len(outer) == 2


def _host_events(log_dir):
    """(name, stats) of every host-plane event in the profile under log_dir."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]


@pytest.mark.parametrize("with_tracer", [False, True])
def test_span_lands_on_profiler_host_plane(tmp_path, with_tracer):
    with trace.capture() if with_tracer else contextlib.nullcontext() as tr:
        with jax.profiler.trace(str(tmp_path)):
            with trace.span("data/to_device", bytes=65904):
                jnp.ones(3).block_until_ready()
    assert ("data/to_device", {"bytes": 65904}) in _host_events(str(tmp_path))
    if with_tracer:
        assert [(e["name"], e["args"]) for e in tr.events] == [
            ("data/to_device", {"bytes": 65904})]
    # neither a tracer nor a profile: the shared null span again
    assert trace.span("data/to_device", bytes=1) is trace._NULL_SPAN


def test_data_plane_spans_carry_round_and_bytes():
    from repro.configs.base import FLConfig
    from repro.data.federated import FederatedPipeline, Population
    from repro.data.tasks import TokenTask
    from repro.fed.rounds import as_device_batch

    fl = FLConfig(num_clients=6, cohort_size=3, sampling="uniform", local_batch=2,
                  imbalance="lognormal", mean_samples=6, seed=1)
    pipe = FederatedPipeline(TokenTask(vocab=64, seq_len=8, num_clients=6),
                             Population.build(fl), fl)
    with trace.capture() as tr:
        batch = as_device_batch(pipe.round_batch(5))
    spans = [(e["name"], e["args"]) for e in tr.events]
    # tokens int32 [C, K_max, B, T+1], mask float32 [C, K_max], and every
    # meta field as a 4-byte [C] scalar
    C, K = 3, pipe.k_max
    meta = sum(a is not None for a in batch.meta)
    nbytes = 4 * (C * K * 2 * 9 + C * K + meta * C)
    # a sequential cohort computes each client's steps up to its last unmasked one
    computed = sum(max((k + 1 for k in range(K) if row[k] > 0), default=0)
                   for row in np.asarray(batch.step_mask))
    assert spans == [("data/index_plan", {"round": 5}), ("data/materialize", {"round": 5}),
                     ("data/local_steps", {"laid_out": C * K, "computed": computed}),
                     ("data/to_device", {"bytes": nbytes})]
    assert nbytes == sum(x.nbytes for x in jax.tree.leaves(batch))


# The round step's named scopes (``jax.named_scope``): op metadata only.

def _round_step_hlo(mode):
    """Compiled HLO text of a tiny dense model's legacy round step."""
    from repro.configs.base import FLConfig
    from repro.configs.paper_tasks import CHARLM_TINY
    from repro.data.federated import FederatedPipeline, Population
    from repro.data.tasks import CharLMTask
    from repro.fed.losses import make_loss
    from repro.fed.rounds import as_device_batch, build_round_step
    from repro.fed.strategy import bind_strategy
    from repro.models.model import build_model

    fl = FLConfig(num_clients=4, cohort_size=2, sampling="uniform", local_batch=2,
                  imbalance="lognormal", mean_samples=4, cohort_mode=mode, seed=0)
    pipe = FederatedPipeline(CharLMTask(vocab=CHARLM_TINY.vocab, seq_len=16, num_clients=4),
                             Population.build(fl), fl)
    model = build_model(CHARLM_TINY)
    loss = make_loss(model)
    strat = bind_strategy(None, fl, loss, num_clients=fl.num_clients)
    step = jax.jit(build_round_step(loss, strat, fl))
    state = strat.init(model.init(jax.random.PRNGKey(0)))
    return step.lower(state, as_device_batch(pipe.round_batch(0))).compile().as_text()


_HLO: dict = {}


def _scoped_hlo(mode):
    if mode not in _HLO:
        _HLO[mode] = _round_step_hlo(mode)
    return _HLO[mode]


def _scope_names(hlo):
    """Every name on the ops' ``op_name`` paths (``transpose(jvp(lm_head))``
    gives ``transpose``, ``jvp``, ``lm_head``)."""
    return {tok for path in re.findall(r'op_name="([^"]*)"', hlo)
            for tok in re.findall(r"[\w.-]+", path)}


@pytest.mark.parametrize("mode", ["sequential", "vmapped"])
def test_round_step_hlo_names_its_layers(mode):
    scopes = _scope_names(_scoped_hlo(mode))
    want = {"local_step", "lm_head", "local_apply", "embed", "blocks",
            "client_delta", "client_transform", "server_update"}
    if mode == "sequential":
        want |= {"accumulate", "agg_coeffs"}
    assert want <= scopes, want - scopes


def _computations(hlo):
    """HLO text -> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if m:
            name, out[m.group(1)] = m.group(1), []
        elif line.startswith("}"):
            name = None
        elif name:
            out[name].append(line)
    return out


@pytest.mark.parametrize("mode", ["sequential", "vmapped"])
def test_local_loop_body_ops_carry_local_step(mode):
    """Every op of the local loop's body names ``local_step``: a profile
    reduction gives an op the compiler made without metadata the scopes its
    computation's ops share, so one stray op there drops the whole step's
    fusions out of the scope."""
    hlo = _scoped_hlo(mode)
    comps = _computations(hlo)
    bodies = [comps[b] for b in set(re.findall(r"body=%?([\w.-]+)", hlo))]
    paths = [re.findall(r'op_name="([^"]*)"', "\n".join(b)) for b in bodies]
    local = [p for p in paths if any("local_apply" in n for n in p)]
    assert local
    assert [n for p in local for n in p if "local_step" not in n] == []


@pytest.mark.parametrize("mode", ["sequential", "vmapped"])
def test_named_scopes_add_no_op(mode, monkeypatch):
    def strip(text):
        # op metadata, and the stack-frame tables it points into (they hold
        # the caller's frames too)
        text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", "",
                      text, flags=re.M)
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    scoped = _scoped_hlo(mode)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _round_step_hlo(mode)
    assert "local_step" in _scope_names(scoped) and "local_step" not in _scope_names(plain)
    assert "FileNames" not in strip(scoped) and " fusion(" in strip(scoped)
    assert strip(scoped) == strip(plain)


# ---------------------------------------------------------------------------
# Recompile sentinels
# ---------------------------------------------------------------------------


def test_sentinel_counts_backend_compiles():
    snt = obs.sentinel()
    base = snt.count

    @jax.jit
    def f(x):
        return x * 2.0

    f(jnp.ones(3))
    f(jnp.ones(3))           # cache hit: no event
    assert snt.count == base + 1
    f(jnp.ones(4))           # new shape: one more compile
    assert snt.count == base + 2


def test_compile_guard_passes_and_raises():
    @jax.jit
    def f(x):
        return x + 1.0

    with obs.compile_guard(f) as g:
        f(jnp.ones(3))
        f(jnp.ones(3))
    assert g.compiles == 1

    with pytest.raises(obs.RecompileError, match="2 compilations"):
        with obs.compile_guard(f, max_compiles=1):
            f(jnp.ones(5))
            f(jnp.ones(6))

    # process-wide form (no fn): counts any backend compile in the block
    with obs.compile_guard(max_compiles=1) as g:
        jax.jit(lambda x: x - 1.0)(jnp.ones(2))
    assert g.compiles == 1

    with pytest.raises(TypeError, match="no executable cache"):
        obs.cache_size(lambda x: x)


def test_compile_observed_as_trace_span():
    with trace.capture() as tr:
        jax.jit(lambda x: x * 3.0)(jnp.ones(7))
    names = [e["name"] for e in tr.events]
    assert "jax/backend_compile" in names


# ---------------------------------------------------------------------------
# Log levels
# ---------------------------------------------------------------------------


def test_log_levels(capsys):
    try:
        set_log_level("debug")
        debug("dbg", a=1)
        log("inf")
        warn("wrn")
        out = capsys.readouterr()
        assert "DEBUG dbg a=1" in out.out and "inf" in out.out
        assert "WARN wrn" in out.err
        set_log_level("warn")
        debug("hidden")
        log("hidden-too")
        warn("visible")
        out = capsys.readouterr()
        assert out.out == "" and "visible" in out.err
        set_log_level("quiet")
        warn("gone")
        out = capsys.readouterr()
        assert out.out == "" and out.err == ""
    finally:
        set_log_level(None)
    with pytest.raises(ValueError):
        set_log_level("loud")


def test_log_level_env(monkeypatch, capsys):
    monkeypatch.setenv("FEDSHUFFLE_LOG", "quiet")
    log("suppressed")
    assert capsys.readouterr().out == ""
    monkeypatch.setenv("FEDSHUFFLE_LOG", "bogus")
    with pytest.raises(ValueError, match="FEDSHUFFLE_LOG"):
        log("boom")
    assert "quiet" in LOG_LEVELS


# ---------------------------------------------------------------------------
# MetricLogger (thin registry client + the CSV union fix)
# ---------------------------------------------------------------------------


def test_metric_logger_csv_union_of_keys():
    ml = MetricLogger(name="t")
    ml.append(round=0, local_loss=2.0)
    ml.append(round=1, local_loss=1.5, eval_acc=0.75)  # mid-run key
    csv = ml.csv()
    lines = csv.splitlines()
    assert lines[0] == "round,local_loss,eval_acc"
    assert lines[1] == "0,2.0,"          # absent cell is empty, not dropped
    assert lines[2] == "1,1.5,0.75"
    assert ml.last()["eval_acc"] == 0.75
    assert len(ml.rows) == 2


def test_metric_logger_print_csv_and_dump(tmp_path):
    import io

    ml = MetricLogger()
    ml.append(a=1)
    ml.append(a=2, b=3)
    buf = io.StringIO()
    ml.print_csv(file=buf)
    out = buf.getvalue().splitlines()
    assert out[0] == "a,b" and out[1] == "1,"
    p = str(tmp_path / "rows.jsonl")
    ml.dump(p)
    assert [json.loads(line)["a"] for line in open(p)] == [1, 2]


def test_metric_logger_device_values():
    ml = MetricLogger()
    ml.append(loss=jnp.float32(1.5), n=2)
    assert ml.rows[0] == {"loss": 1.5, "n": 2}
    assert isinstance(ml.rows[0]["loss"], float)


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_validate_telemetry_config():
    import dataclasses

    from repro.configs.base import FLConfig

    fl = FLConfig(num_clients=4, cohort_size=2)
    obs.validate_telemetry_config(fl)   # default "off" is valid
    for bad, msg in [(dataclasses.replace(fl, telemetry="verbose"),
                      "unknown telemetry mode"),
                     (dataclasses.replace(fl, telemetry_bins=1),
                      "telemetry_bins")]:
        with pytest.raises(ValueError, match=msg):
            obs.validate_telemetry_config(bad)


def test_bind_strategy_validates_telemetry():
    import dataclasses

    from repro.configs.base import FLConfig
    from repro.fed.losses import make_quadratic_loss
    from repro.fed.strategy import bind_strategy

    fl = dataclasses.replace(
        FLConfig(num_clients=4, cohort_size=2), telemetry="everything")
    with pytest.raises(ValueError, match="unknown telemetry mode"):
        bind_strategy(None, fl, make_quadratic_loss(4), num_clients=4)


# ---------------------------------------------------------------------------
# Sink failure isolation: telemetry IO must never kill training
# ---------------------------------------------------------------------------


class _BoomSink:
    """Raises from emit after ``ok_rows`` successes (and from close)."""

    def __init__(self, ok_rows=0):
        self.ok_rows = ok_rows
        self.emitted = 0
        self.closed = False

    def emit(self, record):
        if self.emitted >= self.ok_rows:
            raise OSError("disk full")
        self.emitted += 1

    def close(self):
        self.closed = True
        raise OSError("disk full")


def test_failing_sink_is_disabled_not_fatal(capsys):
    from repro.utils.logging import set_log_level

    mem = obs_metrics.InMemorySink()
    boom = _BoomSink(ok_rows=1)
    reg = obs_metrics.MetricRegistry("t", sinks=[boom, mem])
    try:
        set_log_level("warn")
        reg.emit_row({"round": 0})            # boom succeeds once
        reg.emit_row({"round": 1})            # boom raises -> dropped
        reg.emit_row({"round": 2})            # boom must not run again
        err = capsys.readouterr().err
    finally:
        set_log_level(None)
    assert err.count("metric sink failed") == 1       # exactly one warning
    assert "OSError" in err and "_BoomSink" in err
    assert boom.emitted == 1 and boom.closed          # best-effort close ran
    assert reg.sinks == [mem]                         # healthy sink survives
    assert [r["round"] for r in mem.records] == [0, 1, 2]


def test_failing_sink_close_is_disabled_not_fatal(capsys):
    from repro.utils.logging import set_log_level

    mem = obs_metrics.InMemorySink()
    reg = obs_metrics.MetricRegistry("t", sinks=[_BoomSink(ok_rows=0), mem])
    try:
        set_log_level("warn")
        reg.close()                                   # BoomSink.close raises
        err = capsys.readouterr().err
    finally:
        set_log_level(None)
    assert err.count("metric sink failed") == 1
    assert reg.sinks == [mem]                         # only the bad one dropped


def test_train_loop_survives_failing_sink():
    """End-to-end: a sink dying mid-run costs its rows, not the run."""
    from repro.configs.base import FLConfig
    from repro.data.federated import FederatedPipeline, Population
    from repro.data.tasks import DuplicatedQuadraticTask
    from repro.fed.losses import make_quadratic_loss
    from repro.fed.train_loop import train

    task = DuplicatedQuadraticTask(copies=(1, 2, 3))
    fl = FLConfig(num_clients=3, cohort_size=2, sampling="uniform", epochs=1,
                  local_batch=1, algorithm="fedavg", local_lr=0.05, seed=3)
    pipe = FederatedPipeline(task, Population.build(fl, sizes=task.sizes()), fl)
    res = train(make_quadratic_loss(3), {"x": jnp.zeros(3)}, pipe, fl, 3,
                log_every=0)
    reg = res.registry
    reg.add_sink(_BoomSink(ok_rows=0))
    n = len(reg.sinks)
    reg.emit_row({"round": 99})                       # would have raised
    assert len(reg.sinks) == n - 1                    # only the bad one gone
    assert [r["round"] for r in res.metrics.rows[:3]] == [0, 1, 2]
