"""Launcher routing: ``--smoke`` trains the reduced config, a bare ``--arch``
the published one — checked without training at full width on CPU."""
import os
import subprocess
import sys

import pytest

import repro.launch.train as launch
from repro.configs.registry import get_arch

ROOT = os.path.join(os.path.dirname(__file__), "..")


class _Stop(Exception):
    pass


def _model_cfg_for(monkeypatch, argv):
    """Run ``launch.main`` on ``argv`` up to model building; return the cfg."""
    seen = {}

    def fake_build_model(cfg):
        seen["cfg"] = cfg
        raise _Stop

    monkeypatch.setattr(launch, "build_model", fake_build_model)
    monkeypatch.setattr(launch, "use_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["train"] + argv)
    with pytest.raises(_Stop):
        launch.main()
    return seen["cfg"]


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-1.3b"])
def test_smoke_flag_trains_reduced_config(monkeypatch, arch):
    assert _model_cfg_for(monkeypatch, ["--arch", arch, "--smoke"]) == get_arch(arch).reduced()


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-1.3b"])
def test_bare_arch_trains_published_config(monkeypatch, arch):
    assert _model_cfg_for(monkeypatch, ["--arch", arch]) == get_arch(arch)


def test_full_job_shapes():
    cfg, fl, task = launch.arch_job("qwen1.5-0.5b", smoke=False)
    assert cfg == get_arch("qwen1.5-0.5b")
    assert (fl.num_clients, fl.cohort_size, fl.local_batch, fl.epochs) == (16, 4, 2, 1)
    assert (fl.cohort_mode, fl.imbalance, fl.mean_samples) == ("sequential", "lognormal", 4)
    assert (task.vocab, task.seq_len) == (151936, 512)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert out.stdout == ""
