"""The round step's program, pinned.

For tiny models: the sequential padded identity-uplink round the benchmark's
cells run (dense and DeepSeek-V2-Lite), the vmapped cohort, and a compressed
uplink.  Two digests each: sha256 of ``jax.jit(round_step).lower(state,
batch).as_text()``, the program itself, and of its distinct named-scope op
paths (the names ``bench/scopes.py`` reduces, which the program text leaves
out).  A refactor of the round driver must keep both; a digest moves only
with a change to what the round computes or how its ops are named.
"""
import hashlib
import re

import jax
import pytest

from repro.configs.base import FLConfig
from repro.configs.paper_tasks import CHARLM_TINY
from repro.configs.registry import ARCHS
from repro.data.federated import FederatedPipeline, Population
from repro.data.tasks import CharLMTask, TokenTask
from repro.fed.losses import make_loss
from repro.fed.rounds import as_device_batch, build_round_step
from repro.fed.strategy import bind_strategy
from repro.models.model import build_model


def round_program_text(arch: str | None = None, *, debug_info: bool = False,
                       **fl_kw) -> str:
    """Lowered StableHLO of one round step: CharLM-tiny when ``arch`` is
    None, else ``ARCHS[arch].reduced()``; ``fl_kw`` override the FLConfig."""
    fl = FLConfig(**{**dict(num_clients=4, cohort_size=2, sampling="uniform",
                            local_batch=2, imbalance="lognormal", mean_samples=4,
                            seed=0), **fl_kw})
    if arch is None:
        cfg = CHARLM_TINY
        task = CharLMTask(vocab=cfg.vocab, seq_len=16, num_clients=fl.num_clients)
    else:
        cfg = ARCHS[arch].reduced()
        task = TokenTask(vocab=cfg.vocab, seq_len=16, num_clients=fl.num_clients)
    pipe = FederatedPipeline(task, Population.build(fl), fl)
    model = build_model(cfg)
    loss = make_loss(model)
    strat = bind_strategy(None, fl, loss, num_clients=fl.num_clients)
    state = strat.init(model.init(jax.random.PRNGKey(0)))
    lowered = jax.jit(build_round_step(loss, strat, fl)).lower(
        state, as_device_batch(pipe.round_batch(0)))
    return lowered.as_text(debug_info=debug_info)


def scope_paths(text: str) -> list[str]:
    """The distinct op-name paths of a ``debug_info`` program text
    (``jit(round_step)/accumulate/mul``, ...), sorted; source files and the
    Python functions of the call stack are left out."""
    return sorted({n for n in re.findall(r'loc\("([^"]*)"', text)
                   if "/" in n and not n.startswith("/")})


def _sha(s: str) -> str:
    return hashlib.sha256(s.encode()).hexdigest()


CASES = {
    "dense-sequential": (None, dict(cohort_mode="sequential")),
    "dsv2lite-sequential": ("deepseek-v2-lite-16b", dict(cohort_mode="sequential")),
    "dense-vmapped": (None, dict(cohort_mode="vmapped")),
    "dense-sequential-qsgd": (None, dict(cohort_mode="sequential", uplink="qsgd")),
}

# recorded before the round driver was restructured into stages
PROGRAM_DIGESTS = {
    "dense-sequential": "c4d3d25719c80356ef1949783913ae38cfbccedbc74a2d0bd3489dee5d03a693",
    "dsv2lite-sequential": "37608d450874fb58f1afb9d4b2c1abf532b47b47bd6559dde7453c7d710e25dd",
    "dense-vmapped": "7af6dbcc3a2b06a52a86f1b5d4f98d2f038b2b9a0c779d4a8161571e6ec284e6",
    "dense-sequential-qsgd": "52e9119b7d97142e65fa99bf49ed09da64bee3a17291f0958e70618a7f2181d8",
}
SCOPE_DIGESTS = {
    "dense-sequential": "1a17d3fd25aaae0c2a6388347fd217991416d8c2e52827fa3e1518900852a787",
    "dsv2lite-sequential": "30dd63c4033bf9fcb9cdf977c415e5f123595a43f96b14cda0264a8748db5ab6",
    "dense-vmapped": "2fb3a2e40b64d24fa25c71413b5c69b0af2c335ae85dbc55d36af5b8db3487fc",
    "dense-sequential-qsgd": "e7a9dbae9ec15778e54be12b4bfeb73aa3ade6e3c26f463ddabf0d7216980c5f",
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_step_lowers_to_the_pinned_program(case):
    arch, fl_kw = CASES[case]
    assert _sha(round_program_text(arch, **fl_kw)) == PROGRAM_DIGESTS[case]


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_step_keeps_its_pinned_scope_names(case):
    arch, fl_kw = CASES[case]
    paths = scope_paths(round_program_text(arch, debug_info=True, **fl_kw))
    assert "jit(round_step)/server_update/add" in paths
    assert _sha("\n".join(paths)) == SCOPE_DIGESTS[case]
