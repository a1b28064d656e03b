"""CPU tests of the benchmark harness; run from the repository root with

    python -m pytest -q bench/tests
"""
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Keep CPU tests out of the benchmark's compile cache."""
    from bench import harness

    monkeypatch.setattr(harness, "use_benchmark_cache", lambda: None)


@pytest.fixture
def tiny_root(tmp_path):
    """A benchmark root holding the tiny CPU cells, the real model families
    (beside the test-only ``hetero``), metric readers and peaks, and one extra
    metric file (``dummy_rounds``)."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(DATA, "tiny"), root)
    shutil.copytree(os.path.join(ROOT, "bench", "families"), root / "bench" / "families",
                    dirs_exist_ok=True)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"), root / "bench" / "metrics")
    shutil.copy(os.path.join(ROOT, "bench", "peaks.json"), root / "bench" / "peaks.json")
    (root / "bench" / "metrics" / "dummy_rounds.py").write_text(
        "def read(run):\n    return float(len(run.rounds))\n")
    return str(root)
