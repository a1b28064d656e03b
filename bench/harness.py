"""One run of one cell: set-up, the measured window, and the check.

The window drives what ``repro.fed.train_loop.train`` builds for the legacy
engine: ``build_round_step`` + ``jit_round_step`` over the bound strategy,
fed by ``FederatedPipeline.round_batch`` -> ``as_device_batch``, each round
ending in the host fetch of its loss.  Set-up makes the weights from the
seed, builds that step and state once, and drives the first rounds through
the same call; those rounds are compared with the plain reference after the
window has closed.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import statistics
import tempfile
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, FLConfig
from repro.data.federated import FederatedPipeline, Population
from repro.fed.losses import make_loss
from repro.fed.rounds import as_device_batch, build_round_step, jit_round_step
from repro.fed.strategy import bind_strategy
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.obs import sentinels

from . import clients, compare, reference, spec, trace_reduce
from .flops import train_flops_per_token
from .weights import change_norms, init_params


def annotate(name: str):
    """A host span on the profiler's clock (free while no trace runs)."""
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_PREFIX + name)


def arch_config(s: spec.ModelShape, name: str) -> ArchConfig:
    return ArchConfig(
        name=name, family="dense", n_layers=s.layers, d_model=s.d_model,
        n_heads=s.heads, n_kv_heads=s.kv_heads,
        head_dim=0 if s.heads * s.head_dim == s.d_model else s.head_dim,
        d_ff=s.d_ff, vocab=s.vocab, qkv_bias=s.qkv_bias, rope_theta=s.rope_theta,
        tie_embeddings=s.tied, norm_eps=s.norm_eps, dtype=s.dtype)


@dataclass
class RoundRecord:
    rnd: int
    loss: float
    useful_steps: int     # unmasked local steps over the cohort
    padded_steps: int     # C * K_max steps the padded scan computes
    plan_s: float         # host seconds in round_batch + as_device_batch
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

    def start(self, seed: int) -> None:
        task = clients.TokenRows(self.shape.vocab, self.cell.traffic["seq_len"], seed)
        self.pipe = FederatedPipeline(task, self.population, self.fl)
        # the strategy's init copies the weights; the rounds donate the copy
        self.state = self.strat.init(init_params(self.shape, seed))

    def round(self, r: int) -> RoundRecord:
        t0 = time.perf_counter()
        with annotate("round_batch"):
            rb = self.pipe.round_batch(r)
        with annotate("as_device_batch"):
            batch = as_device_batch(rb)
        t1 = time.perf_counter()
        with annotate("dispatch"):
            self.state, mets = self.step(self.state, batch, self.lr_mult)
        t2 = time.perf_counter()
        with annotate("metrics_fetch"):
            loss = float(mets["local_loss"])
        self.batch = batch
        mask = rb.step_mask
        return RoundRecord(r, loss, int(mask.sum()), int(mask.size), t1 - t0, t2 - t1,
                           time.perf_counter() - t2, (rb.meta.client_id, mask, rb.data["tokens"]))

    def warm_up(self, seed: int, rounds: int) -> reference.Readings:
        """Rounds 0..rounds-1 through the window's own call, with what the
        comparison reads of them."""
        records, grad_norms = [], None
        for r in range(rounds):
            records.append(self.round(r))
            if r == 0:
                grad_norms = change_norms(self.shape, self.state.params, seed)
        return reference.Readings(
            [x.loss for x in records], grad_norms,
            change_norms(self.shape, self.state.params, seed),
            [x.inputs for x in records])

    def step_memory(self) -> dict:
        """The compiler's memory analysis of the round step the window ran."""
        ma = self.step.lower(self.state, self.batch, self.lr_mult).compile().memory_analysis()
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
    trace: dict | None = None         # trace_reduce.reduce() of a traced window

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
                while True:
                    i = len(window)
                    window.append(prog.round(rounds_checked + (i % cycle if cycle else i)))
                    if (time.perf_counter() - t_w0 >= seconds
                            and not (cycle and len(window) % cycle)):
                        break
            t_w1 = time.perf_counter()
            gc.callbacks.remove(gcs)
            compiles_in_window = snt.count - compiles0
        reduced = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(tmp))) \
            if trace else None
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    stats = dev.memory_stats() or {}
    step_mem = prog.step_memory()
    memory_peak = max(int(stats.get("peak_bytes_in_use", 0)), step_mem["peak_memory_in_bytes"])
    prog.free()
    slow = max(window, key=lambda w: w.plan_s + w.dispatch_s + w.fetch_s)
    print(f"window: {len(window)} rounds of rounds {min(w.rnd for w in window)}.."
          f"{max(w.rnd for w in window)} in "
          f"{t_w1 - t_w0!r} s; compiles in window: {compiles_in_window}", flush=True)
    print(f"slowest round: {slow.rnd}, plan {slow.plan_s!r} s, dispatch {slow.dispatch_s!r} s, "
          f"fetch {slow.fetch_s!r} s (median round "
          f"{statistics.median(w.plan_s + w.dispatch_s + w.fetch_s for w in window)!r} s); "
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
        peak_flops=float(peak["bf16_flops_per_s"]), chips=cell.chips, trace=reduced)
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
