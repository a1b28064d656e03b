"""A whole run on the CPU at a tiny size: data-driven lookup, the window's
counts, and the check against the reference with faults planted under the
timed path."""
import time

import jax
import numpy as np
import pytest

from bench import clients, harness, reference, spec

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
