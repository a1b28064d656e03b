"""DeepSeek-V2 in the program against the benchmark's plain reference
(``bench/families/deepseek_v2.py``): YaRN at the published sizes, the loss
and one SGD step of a tiny model with its leading dense layer, and the
reference's own layer-by-layer step against autodiff of its whole loss."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from repro.configs.base import YaRNConfig
from repro.configs.registry import ARCHS
from repro.models.attention import mla_softmax_scale
from repro.models.layers import yarn_frequencies, yarn_mscale
from repro.models.model import build_model

FAM = spec.family("deepseek_v2")

# DeepSeek-V2-Lite's rope_scaling at its published rope width 64
V2_LITE = ARCHS["deepseek-v2-lite-16b"]


def _tiny(dtype="float32", mscale=0.707):
    """1 dense + 2 MoE layers, 4 of 8 experts held from expert 2, top-3."""
    return FAM.Shape(layers=3, first_dense=1, d_model=64, heads=4, qk_nope=16, qk_rope=16,
                     v_dim=16, kv_lora=32, d_ff=96, expert_ff=32, experts=8, held=4,
                     first_held=2, top_k=3, shared=1, vocab=128, rope_theta=10000.0,
                     yarn_factor=40.0, yarn_original_max=4096, yarn_beta_fast=32.0,
                     yarn_beta_slow=1.0, yarn_mscale=mscale, yarn_mscale_all_dim=0.707,
                     norm_eps=1e-6, dtype=dtype, init_std=0.1, aux_alpha=0.05)


def test_yarn_frequencies_and_mscale_at_the_published_sizes():
    """Correction range floor(10.47) = 10 to ceil(22.51) = 23 for dim 64:
    pairs below it keep theta^(-2i/64), pairs from 23 on take that over 40,
    a linear ramp between; mscale = 0.1 * 0.707 * ln 40 + 1."""
    y = V2_LITE.mla.yarn
    assert (y.factor, y.original_max, y.beta_fast, y.beta_slow) == (40.0, 4096, 32.0, 1.0)
    f = yarn_frequencies(64, 10000.0, y)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    lo, hi = 10, 23
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))) == lo
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == hi
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(f, base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f[:lo + 1], base[:lo + 1], rtol=1e-6)
    np.testing.assert_allclose(f[hi:], base[hi:] / 40, rtol=1e-6)
    # the benchmark's reference computes them on its own
    np.testing.assert_allclose(f, FAM.yarn_inv_freq(FAM.Shape.from_config(
        spec.load_cell("dsv2lite-xdev-equal").config)), rtol=1e-6)
    m = yarn_mscale(40.0, 0.707)
    assert abs(m - 1.26080) < 1e-5 and abs(m * m - 1.58963) < 1e-5
    assert abs(mla_softmax_scale(V2_LITE) - 1.58963 / math.sqrt(192)) < 1e-6
    assert abs(mla_softmax_scale(V2_LITE) - 0.114721) < 1e-6
    assert yarn_mscale(1.0, 0.707) == 1.0


def test_reduced_deepseek_v2_lite_keeps_its_dense_layer_and_yarn():
    cfg = V2_LITE.reduced()
    assert cfg.first_dense == 1 and cfg.n_layers == 2
    assert cfg.mla.yarn == V2_LITE.mla.yarn == YaRNConfig()
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert params["first"]["mlp"]["gate"].shape == (1, cfg.d_model, cfg.d_ff)
    assert params["blocks"]["moe"]["router"].shape[0] == 1


def test_published_config_builds_at_its_widths():
    """The cell's configuration through ``arch_config`` and ``build_model``:
    the program's parameter tree is the family's layout, 936,684,032
    parameters (1 dense + 8 MoE layers, 8 of 64 experts, 12,800 rows)."""
    cell = spec.load_cell("dsv2lite-xdev-equal")
    s = cell.shape
    assert (s.layers, s.first_dense, s.experts, s.held, s.top_k, s.vocab) == (9, 1, 64, 8, 6,
                                                                            12800)
    params = jax.eval_shape(build_model(FAM.arch_config(s, cell.config_name)).init,
                            jax.random.PRNGKey(0))
    got = {k: v.shape for k, v in weights.flatten(params).items()}
    assert got == {k: shape for k, (shape, _) in FAM.leaf_shapes(s).items()}
    assert FAM.param_count(s) == cell.config["params"] == 936_684_032
    assert abs(FAM.train_flops_per_token(s, 512) / 1e9 - 2.594) < 1e-3


def _program_step(s, x, toks, eta):
    model = build_model(FAM.arch_config(s, "tiny-ds"))
    (loss, mets), g = jax.value_and_grad(model.loss, has_aux=True)(x, {"tokens": toks})
    return jax.tree.map(lambda w, d: w - eta * d, x, g), loss, mets


@pytest.mark.parametrize("mscale", [0.707, 1.0])
def test_program_loss_and_step_match_the_reference(mscale):
    """``Model.loss`` and one SGD step of the tiny model on the benchmark's
    seeded weights against ``reference.local_step``.  Both sides are float32
    on the CPU, so they differ only in summation order and in the program's
    grouped dispatch (the reference runs every held expert on every token):
    the loss agrees to 1e-5 relative, each leaf's change to 1e-4 of its
    norm (a routing flip would move a leaf's change by far more).  YaRN's
    mscale 1.0 against mscale_all_dim 0.707 scales the rotated rope part by
    their ratio."""
    s = _tiny(mscale=mscale)
    x = weights.init_params(s, 11)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 33), 0, s.vocab)
    mine, loss, mets = _program_step(s, x, toks, 0.1)
    with jax.default_matmul_precision("highest"):
        want, want_loss = reference.local_step(s, jax.tree.map(jnp.copy, x), toks, 0.1)
    assert abs(float(loss) - want_loss) <= 1e-5 * abs(want_loss)
    assert float(mets["aux"]) > 0 and "moe_rows" in mets
    # every held (token, choice) pair of both MoE layers was computed
    assert 0 < int(mets["moe_rows"]) <= 2 * 2 * 32 * s.top_k
    fx, fm, fw = (weights.flatten(t) for t in (x, mine, want))
    assert set(fm) == set(fw)
    for k in fw:
        d_ref = np.asarray(fw[k] - fx[k])
        d_prog = np.asarray(fm[k] - fx[k])
        assert np.linalg.norm(d_prog - d_ref) <= 1e-4 * max(np.linalg.norm(d_ref), 1e-12), k
    for k in ("first/mlp/gate", "blocks/moe/experts/up", "blocks/moe/router", "lm_head"):
        assert np.linalg.norm(np.asarray(fw[k] - fx[k])) > 0, k


def test_reference_layerwise_step_equals_autodiff_of_its_whole_loss():
    """The reference's layer-by-layer step (each layer's backward with
    cotangent (dh, 1) for its balance loss) against ``jax.grad`` of cross
    entropy plus every layer's balance loss in one function."""
    s = _tiny()
    x = weights.init_params(s, 3)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, s.vocab)

    def loss(p):
        h = p["embed"][toks[:, :-1]]
        aux = 0.0
        for path, l, fn in FAM.layers(s):
            stack = jax.tree.map(lambda t: t[l], p[path])
            h, a = fn(s, reference.no_quant, stack, h)
            aux = aux + a
        h = reference.rmsnorm(p["final_norm"]["scale"], h, s.norm_eps)
        logits = jnp.matmul(h, p["lm_head"], precision=reference.HIGHEST)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ce = jnp.mean(lse - jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0])
        return ce + aux, aux

    with jax.default_matmul_precision("highest"):
        (want_loss, aux), g = jax.value_and_grad(loss, has_aux=True)(x)
        got, got_loss = reference.local_step(s, jax.tree.map(jnp.copy, x), toks, 0.1)
    assert float(aux) > 0
    assert abs(got_loss - float(want_loss)) < 1e-6 * abs(float(want_loss)) + 1e-7
    want = weights.flatten(jax.tree.map(lambda w, d: w - 0.1 * d, x, g))
    got = weights.flatten(got)
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-5, atol=1e-7, err_msg=k)


@pytest.mark.parametrize("field", ["held", "first_held"])
def test_other_shares_route_alike_and_differ_in_experts(field):
    """Another chip's share: the same router and balance loss, other experts
    computed, so a different output."""
    s = _tiny()
    other = FAM.Shape(**{**s.__dict__, "first_held": 0}) if field == "first_held" else \
        FAM.Shape(**{**s.__dict__, "held": 2})
    x = weights.init_params(s, 4)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, s.d_model))
    p = jax.tree.map(lambda t: t[0], x["blocks"])
    p_other = {**p, "moe": {**p["moe"], "experts": jax.tree.map(
        lambda t: t[:other.held], p["moe"]["experts"])}}
    with jax.default_matmul_precision("highest"):
        y, a = FAM.moe_layer(s, reference.no_quant, p, h)
        y2, a2 = FAM.moe_layer(other, reference.no_quant, p_other, h)
    assert float(a) == float(a2)
    assert float(jnp.max(jnp.abs(y - y2))) > 0
