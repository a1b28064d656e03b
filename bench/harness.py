"""One run of one cell: set-up, the measured window, and the check.

The window drives what ``repro.fed.train_loop.train`` builds for the legacy
engine: ``build_round_step`` + ``jit_round_step`` over the bound strategy,
fed by ``FederatedPipeline.round_batch`` -> ``as_device_batch``, and fetches
every round's loss to the host.  It keeps up to ``AHEAD_S`` seconds of rounds
dispatched ahead of the loss it waits for, so that the chip stays fed while
the host stands still (the device runtime may hold the host back sooner, in
``as_device_batch``); when its time is up it sends nothing more, waits for
every round it sent, and only then reads the clock.  Set-up makes the weights
from the seed, builds that step and state once, and drives the first rounds
through the same call, one round at a time; those rounds are compared with the
plain reference after the window has closed.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import math
import shutil
import statistics
import tempfile
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.data.federated import FederatedPipeline, Population
from repro.fed import rounds as fed_rounds
from repro.fed.losses import make_loss
from repro.fed.rounds import as_device_batch, build_round_step, jit_round_step
from repro.fed.strategy import bind_strategy
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.obs import sentinels

from . import clients, compare, reference, scopes, spec, trace_reduce
from .flops import train_flops_per_token
from .weights import change_norms, init_params

AHEAD_S = 5.0   # device seconds of rounds queued behind the loss the window waits for


def annotate(name: str):
    """A host span on the profiler's clock (free while no trace runs)."""
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_PREFIX + name)


def arch_config(s, name: str):
    """The program's ``ArchConfig`` of the family shape ``s``."""
    return spec.family_of(s).arch_config(s, name)


def computed_steps(rb) -> int:
    """Local steps the program computes of a round's batch: what
    ``repro.fed.rounds.local_steps`` says, or every slot of the ``[C, K_max]``
    layout where the program has no such function."""
    local_steps = getattr(fed_rounds, "local_steps", None)
    return local_steps(rb)[1] if local_steps is not None else int(rb.step_mask.size)


@dataclass
class RoundRecord:
    rnd: int
    loss: float
    useful_steps: int     # unmasked local steps over the cohort
    padded_steps: int     # C * K_max steps the cohort layout holds
    computed_steps: int   # of those, the steps the program computed
    plan_s: float         # host seconds in round_batch
    transfer_s: float     # host seconds in as_device_batch, any wait for the runtime's queue with them
    dispatch_s: float     # host seconds in the step's call
    fetch_s: float        # host seconds waiting for the round's loss
    inputs: tuple         # (client ids [C], step mask [C, K], tokens [C, K, B, T+1])


class Program:
    """The system under test, built once; ``start`` gives it a seed's
    weights and token streams."""

    def __init__(self, cell: spec.Cell):
        self.cell = cell
        self.shape = cell.shape
        self.fl = FLConfig(**cell.traffic["fl"])
        self.loss_fn = make_loss(build_model(arch_config(self.shape, cell.config_name)))
        self.strat = bind_strategy(None, self.fl, self.loss_fn,
                                   num_clients=self.fl.num_clients)
        self.step = jit_round_step(build_round_step(self.loss_fn, self.strat, self.fl))
        self.population = Population.build(
            self.fl, sizes=clients.client_sizes(cell.traffic["clients"], self.fl.num_clients))
        self.lr_mult = jnp.float32(1.0)
        self.pipe = self.state = self.batch = None
        self.warm: list = []

    def start(self, seed: int) -> None:
        task = clients.TokenRows(self.shape.vocab, self.cell.traffic["seq_len"], seed)
        self.pipe = FederatedPipeline(task, self.population, self.fl)
        # the strategy's init copies the weights; the rounds donate the copy
        self.state = self.strat.init(init_params(self.shape, seed))

    def send(self, r: int) -> tuple[RoundRecord, dict]:
        """Round ``r``'s batch and step, dispatched; its loss not yet read."""
        t0 = time.perf_counter()
        with annotate("round_batch"):
            rb = self.pipe.round_batch(r)
        t1 = time.perf_counter()
        with annotate("as_device_batch"):
            batch = as_device_batch(rb)
        t2 = time.perf_counter()
        with annotate("dispatch"):
            self.state, mets = self.step(self.state, batch, self.lr_mult)
        t3 = time.perf_counter()
        self.batch = batch
        mask = rb.step_mask
        return RoundRecord(r, math.nan, int(mask.sum()), int(mask.size), computed_steps(rb),
                           t1 - t0, t2 - t1, t3 - t2, 0.0,
                           (rb.meta.client_id, mask, rb.data["tokens"])), mets

    @staticmethod
    def fetch(rec: RoundRecord, mets: dict) -> RoundRecord:
        """Wait for a sent round's loss and read it to the host."""
        t0 = time.perf_counter()
        with annotate("metrics_fetch"):
            rec.loss = float(mets["local_loss"])
        rec.fetch_s = time.perf_counter() - t0
        return rec

    def round(self, r: int) -> RoundRecord:
        return self.fetch(*self.send(r))

    def warm_up(self, seed: int, rounds: int) -> reference.Readings:
        """Rounds 0..rounds-1 through the window's own call, one at a time,
        with what the comparison reads of them."""
        records, grad_norms = [], None
        for r in range(rounds):
            records.append(self.round(r))
            if r == 0:
                grad_norms = change_norms(self.shape, self.state.params, seed)
        self.warm = records
        return reference.Readings(
            [x.loss for x in records], grad_norms,
            change_norms(self.shape, self.state.params, seed),
            [x.inputs for x in records])

    def ahead_steps(self) -> int:
        """Computed steps worth ``AHEAD_S`` device seconds, by the fastest
        warm-up round after the first (which compiles or loads), each of
        those taken one round at a time."""
        step_s = min((w.plan_s + w.transfer_s + w.dispatch_s + w.fetch_s)
                     / max(w.computed_steps, 1)
                     for w in self.warm[1:])
        return max(1, math.ceil(AHEAD_S / step_s))

    def compile_step(self):
        """The round step the window ran, compiled ahead of time for the
        last round's batch (a persistent-cache load after the first run)."""
        return self.step.lower(self.state, self.batch, self.lr_mult).compile()

    @staticmethod
    def step_memory(compiled) -> dict:
        """The compiler's memory analysis of the compiled round step."""
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, k)) for k in (
            "peak_memory_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}

    def free(self) -> None:
        self.state = self.pipe = self.batch = None
        gc.collect()


@dataclass
class RunRecord:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    setup_s: float
    compile_s: float                  # compile-or-load seconds during set-up
    window_s: float
    rounds: list                      # RoundRecord of each window round
    tokens_per_step: int              # local_batch * seq_len
    flops_per_token: float
    peak_flops: float                 # per chip, bf16
    chips: int
    computed_steps: int               # local steps computed over the window
    cell: spec.Cell                   # shape, traffic and config of the run
    trace: dict | None = None         # trace_reduce.reduce() of a traced window
    scopes: dict | None = None        # scopes.reduce() of a traced window with device ops

    @property
    def useful_tokens(self) -> int:
        return sum(r.useful_steps for r in self.rounds) * self.tokens_per_step


class GcWatch:
    """A ``gc.callbacks`` hook: how many collections ran, and the longest."""

    def __init__(self):
        self.count, self.longest, self._t0 = 0, 0.0, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.longest = max(self.longest, time.perf_counter() - self._t0)


def profiler_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def use_benchmark_cache() -> None:
    """JAX's persistent compile cache at the program's fixed path, holding
    every program however fast it compiled, so a second run loads them all."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    use_compile_cache()


def scope_reduction(xplane: str, hlo_text: str) -> dict | None:
    """``scopes.reduce`` of a traced window against the round step's HLO;
    None where the trace holds no device operations (a CPU run)."""
    st = scopes.load(xplane)
    return scopes.reduce(st, hlo_text) if any(st.ops) else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, t_start: float,
             device_kind: str | None = None, root: str = spec.ROOT) -> dict:
    """Set up, measure, check.  Returns the result object the CLI prints;
    ``device_kind`` names the row of ``bench/peaks.json`` (the device's own
    kind when None); ``root`` holds ``BENCHMARK.json`` and ``bench/``."""
    use_benchmark_cache()
    snt = sentinels.sentinel()
    dev = jax.devices()[0]
    peak = spec.peaks(device_kind or dev.device_kind, root)
    rounds_checked = int(cell.limits["rounds"])

    prog = Program(cell)
    prog.start(seed)
    prog_readings = prog.warm_up(seed, rounds_checked)
    compile_s = snt.secs
    ahead = prog.ahead_steps()

    cycle = cell.traffic.get("window_cycle")
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window: list = []
    gcs = GcWatch()
    try:
        with (jax.profiler.trace(tmp, profiler_options=profiler_options())
              if trace else contextlib.nullcontext()):
            compiles0 = snt.count
            gc.callbacks.append(gcs)
            t_w0 = time.perf_counter()
            with annotate("window"):
                sent = collections.deque()   # (record, metrics) of rounds not yet fetched
                queued = 0                   # their computed steps
                while True:
                    i = len(window)
                    rec, mets = prog.send(rounds_checked + (i % cycle if cycle else i))
                    window.append(rec)
                    sent.append((rec, mets))
                    queued += rec.computed_steps
                    # wait for the oldest round only while enough work stands behind it
                    while queued - sent[0][0].computed_steps >= ahead:
                        queued -= prog.fetch(*sent.popleft()).computed_steps
                    if (time.perf_counter() - t_w0 >= seconds
                            and not (cycle and len(window) % cycle)):
                        break
                while sent:
                    prog.fetch(*sent.popleft())
            t_w1 = time.perf_counter()
            gc.callbacks.remove(gcs)
            compiles_in_window = snt.count - compiles0
        stats = dev.memory_stats() or {}
        compiled = prog.compile_step()
        reduced = scoped = None
        if trace:
            path = trace_reduce.find_xplane(tmp)
            reduced = trace_reduce.reduce(trace_reduce.load(path))
            t_s0 = time.perf_counter()
            scoped = scope_reduction(path, compiled.as_text())
            print(f"scopes: {json.dumps(scoped)}", flush=True)
            print(f"scope reduction: {time.perf_counter() - t_s0!r} s", flush=True)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    step_mem = prog.step_memory(compiled)
    memory_peak = max(int(stats.get("peak_bytes_in_use", 0)), step_mem["peak_memory_in_bytes"])
    prog.free()
    # a send is the host's own work; a fetch mostly waits for the device's pace
    slow = max(window, key=lambda w: w.plan_s + w.transfer_s + w.dispatch_s)
    print(f"window: {len(window)} rounds of rounds {min(w.rnd for w in window)}.."
          f"{max(w.rnd for w in window)} in "
          f"{t_w1 - t_w0!r} s, {ahead} computed steps kept ahead; "
          f"compiles in window: {compiles_in_window}", flush=True)
    print(f"slowest send: {window.index(slow) + 1} of {len(window)}, round {slow.rnd}, "
          f"plan {slow.plan_s!r} s, transfer {slow.transfer_s!r} s, dispatch {slow.dispatch_s!r} s "
          f"(median send {statistics.median(w.plan_s + w.transfer_s + w.dispatch_s for w in window)!r}"
          f" s); "
          f"longest fetch {max(w.fetch_s for w in window)!r} s; "
          f"gc in window: {gcs.count} collections, longest {gcs.longest!r} s", flush=True)
    print(f"memory: allocator {stats}; round step {step_mem}", flush=True)

    with jax.default_matmul_precision("highest"):
        ref = reference.reference_rounds(cell.shape, cell.traffic, seed, seed, rounds_checked)
    values = compare.numbers(prog_readings, ref, [w.loss for w in window])
    values["compiles_in_window"] = compiles_in_window
    checks = compare.checks(values, cell.limits)

    run = RunRecord(
        setup_s=t_w0 - t_start, compile_s=compile_s, window_s=t_w1 - t_w0, rounds=window,
        tokens_per_step=prog.fl.local_batch * cell.traffic["seq_len"],
        flops_per_token=train_flops_per_token(cell.shape, cell.traffic["seq_len"]),
        peak_flops=float(peak["bf16_flops_per_s"]), chips=cell.chips,
        computed_steps=sum(w.computed_steps for w in window), cell=cell,
        trace=reduced, scopes=scoped)
    bench = spec.load_benchmark(root)
    metrics = {}
    for m in spec.metrics_for(bench, cell.name, trace):
        value = spec.metric_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": compare.all_within(checks), "attempted": len(window),
              "failed": sum(1 for w in window if not math.isfinite(w.loss)),
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result
