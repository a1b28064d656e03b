"""A whole run on the CPU at a tiny size: data-driven lookup, the window's
counts, and the check against the reference with faults planted under the
timed path."""
import json
import os
import time

import jax
import numpy as np
import pytest

from bench import clients, harness, reference, spec
from bench import scopes as sc
from bench.tests import test_scopes

KIND = "TPU v5 lite"   # the peaks row the CPU run borrows for mfu


def run(root, cell, seconds=0.5, trace=False):
    return harness.run_cell(spec.load_cell(cell, root), 2 ** 33 + 5, seconds, trace,
                            time.perf_counter(), device_kind=KIND, root=root)


def test_cell_and_metric_files_are_found_by_name(tiny_root):
    res = run(tiny_root, "tiny-equal")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"client_tokens_per_s", "setup_s"}
    assert res["metrics"]["client_tokens_per_s"]["unit"] == "tokens/s"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_per_layer_metrics_follow_workloads_key(tiny_root, monkeypatch):
    # no device plane on the CPU: the idle-share reader finds nothing to read
    monkeypatch.setattr(harness.trace_reduce, "reduce", lambda t: None)
    bench = spec.load_benchmark(tiny_root)
    names = [m["name"] for m in spec.metrics_for(bench, "tiny-equal", trace=True)]
    assert names == ["useful_step_share", "device_idle_share", "dummy_rounds"]
    assert "dummy_rounds" not in [m["name"] for m in
                                  spec.metrics_for(bench, "tiny-lognormal", trace=True)]
    res = run(tiny_root, "tiny-equal", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["useful_step_share"]["value"] == 1.0
    assert res["metrics"]["dummy_rounds"]["value"] == res["attempted"]
    assert "device_idle_share" not in res["metrics"]


def test_padded_schedule_share(tiny_root, monkeypatch):
    """The lognormal cell's window repeats whole cycles of rounds 3..6; its
    padded scan computes K_max steps per slot, and the share of them that
    train is the cycle's."""
    monkeypatch.setattr(harness.trace_reduce, "reduce", lambda t: None)
    res = run(tiny_root, "tiny-lognormal", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] % 4 == 0
    cell = spec.load_cell("tiny-lognormal", tiny_root)
    k_max = int(np.max(-(-clients.client_sizes(cell.traffic["clients"], 32) // 2)))
    useful = sum(len(w.tokens) for r in range(3, 7)
                 for w in reference.round_plan(cell.traffic, 256, 1, r))
    share = res["metrics"]["useful_step_share"]["value"]
    assert share == pytest.approx(useful / (4 * 4 * k_max))


def _run_records(monkeypatch) -> list:
    """The ``RunRecord`` each metric reader is handed, in a list."""
    runs, real = [], spec.metric_reader

    def reader(name, root=spec.ROOT):
        read = real(name, root)

        def record(run):
            runs.append(run)
            return read(run)
        return record

    monkeypatch.setattr(harness.spec, "metric_reader", reader)
    return runs


@pytest.mark.parametrize("cell", ["tiny-equal", "tiny-lognormal"])
def test_window_reads_every_loss_however_far_ahead(tiny_root, monkeypatch, cell):
    """Rounds dispatched ahead of the loss the window waits for compute what
    rounds fetched one at a time do: each round's loss is read, after the
    close at the latest, and equals the one-at-a-time run's bitwise."""
    runs = _run_records(monkeypatch)
    losses = []
    for ahead_s in (0.0, 1e9):   # one round behind the wait; every round ahead
        monkeypatch.setattr(harness, "AHEAD_S", ahead_s)
        res = run(tiny_root, cell)
        assert res["correct"], res["checks"]
        losses.append([w.loss for w in runs[-1].rounds])
        assert all(np.isfinite(losses[-1])) and all(w.fetch_s >= 0 for w in runs[-1].rounds)
    n = min(map(len, losses))
    assert n >= 1 and losses[0][:n] == losses[1][:n]


def test_plan_ms_leaves_out_the_transfer(tiny_root, monkeypatch):
    """``plan_ms`` reads ``round_batch`` alone: time spent in
    ``as_device_batch`` (where a run ahead waits for the runtime's queue)
    lands in each round's ``transfer_s`` and not in ``plan_ms``."""
    runs = _run_records(monkeypatch)
    real = harness.as_device_batch

    def slow_transfer(rb):
        time.sleep(0.05)
        return real(rb)

    monkeypatch.setattr(harness, "as_device_batch", slow_transfer)
    res = run(tiny_root, "tiny-equal")
    assert res["correct"], res["checks"]
    rounds = runs[-1].rounds
    assert all(w.transfer_s >= 0.05 for w in rounds)
    assert spec.metric_reader("plan_ms")(runs[-1]) == pytest.approx(
        1e3 * sum(w.plan_s for w in rounds) / len(rounds))
    assert spec.metric_reader("plan_ms")(runs[-1]) < 50


def test_traced_run_records_computed_steps(tiny_root, monkeypatch):
    """A traced CPU run: the record carries its cell and the local steps the
    program computed (each client up to its last unmasked step, fewer than
    the layout's C * K_max); the CPU trace has no device plane, so no scope
    reduction and none of the scope metrics."""
    monkeypatch.setattr(harness.trace_reduce, "reduce", lambda t: None)
    runs = _run_records(monkeypatch)
    res = run(tiny_root, "tiny-lognormal", trace=True)
    assert res["correct"], res["checks"]
    rec = runs[0]
    assert rec.scopes is None and rec.cell.name == "tiny-lognormal"
    per_cycle = sum(len(w.tokens) for r in range(3, 7)
                    for w in reference.round_plan(rec.cell.traffic, 256, 1, r))
    assert rec.computed_steps == res["attempted"] // 4 * per_cycle
    assert rec.computed_steps < sum(w.padded_steps for w in rec.rounds)
    assert not set(res["metrics"]) & set(test_scopes.SEVEN)


def test_scope_metrics_reach_the_result_line(tiny_root, monkeypatch):
    """With device ops in the trace (the synthetic one of ``test_scopes``),
    the reduction goes on the record and the seven readers put it on the
    result line, per computed step and per round."""
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += [m for m in spec.load_benchmark()["per_layer"]
                           if m["name"] in test_scopes.SEVEN]
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(harness.trace_reduce, "reduce", lambda t: None)
    reduce = sc.reduce
    monkeypatch.setattr(harness.scopes, "load", lambda path: test_scopes.synthetic())
    monkeypatch.setattr(harness.scopes, "reduce", lambda t, hlo: reduce(t, test_scopes.HLO))
    runs = _run_records(monkeypatch)
    res = run(tiny_root, "tiny-equal", trace=True)
    assert res["correct"], res["checks"]
    rec = runs[0]
    assert rec.scopes == reduce(test_scopes.synthetic(), test_scopes.HLO)
    want = sc.per_layer(rec.scopes, res["attempted"], rec.computed_steps)
    assert rec.computed_steps == 16 * res["attempted"]
    assert {m: res["metrics"][m]["value"] for m in test_scopes.SEVEN} == want


def _state_unchanged(real):
    def build(*a, **k):
        step = real(*a, **k)

        def broken(state, batch, lr_mult=1.0):
            new, mets = step(state, batch, lr_mult)
            return state._replace(rnd=new.rnd), mets
        return broken
    return build


def _half_batch(real):
    def make(model):
        loss = real(model)

        def broken(params, batch):
            rows = batch["tokens"].shape[0] // 2
            return loss(params, {**batch, "tokens": batch["tokens"][:rows]})
        return broken
    return make


@pytest.mark.parametrize("fault,attr,wrap", [
    ("state_unchanged", "build_round_step", _state_unchanged),
    ("half_batch", "make_loss", _half_batch),
])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, fault, attr, wrap):
    monkeypatch.setattr(harness, attr, wrap(getattr(harness, attr)))
    res = run(tiny_root, "tiny-lognormal")
    assert not res["correct"], (fault, res["checks"])
    failed = [k for k, c in res["checks"].items() if not c["value"] <= c["limit"]]
    assert failed, fault


def test_tpu_check_refuses_cpu(capsys):
    from bench import run as cli

    assert jax.devices()[0].platform == "cpu"
    assert cli.main(["--workload", "qwen05b-xdev-equal", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
