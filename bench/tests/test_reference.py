"""The plain reference: its layer-by-layer step against autodiff of the same
loss, and the lower-precision control against the bf16 program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, reference, spec, weights


@pytest.mark.parametrize("name", ["tiny-lognormal", "tiny-hetero"])
def test_layerwise_step_matches_autodiff(tiny_root, name):
    """The layer-by-layer step against ``jax.grad`` of the whole loss, cross
    entropy plus every layer's auxiliary loss: a dense stack (tied head, no
    auxiliary loss) and the test-only ``hetero`` family (a first layer of
    another width under its own subtree, a nonzero auxiliary loss in every
    layer, separate head)."""
    cell = spec.load_cell(name, tiny_root)
    s = cell.shape
    toks = reference.round_plan(cell.traffic, s.vocab, 7, 0)[0].tokens[0]
    x = weights.init_params(s, 7)

    def loss(p):
        h = p["embed"][toks[:, :-1]]
        aux = 0.0
        for path, l, fn in cell.family.layers(s):
            stack = weights.nest({k[len(path) + 1:]: v for k, v in weights.flatten(p).items()
                                  if k.startswith(path + "/")})
            h, a = fn(s, reference.no_quant, jax.tree.map(lambda t: t[l], stack), h)
            aux = aux + a
        h = reference.rmsnorm(p["final_norm"]["scale"], h, s.norm_eps)
        logits = jnp.matmul(h, p["embed"].T if s.tied else p["lm_head"],
                            precision=reference.HIGHEST)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]) + aux

    want_loss, g = jax.value_and_grad(loss)(x)
    want = jax.tree.map(lambda w, d: w - 0.1 * d, x, g)
    with jax.default_matmul_precision("highest"):
        got, got_loss = reference.local_step(s, jax.tree.map(jnp.copy, x), toks, 0.1)
    assert abs(got_loss - float(want_loss)) < 1e-6
    assert set(weights.flatten(got)) == set(weights.flatten(want))
    for k, v in weights.flatten(want).items():
        np.testing.assert_allclose(weights.flatten(got)[k], v, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("seed", [3, 2 ** 40 + 7])
def test_fp8_control_fails_where_bf16_program_passes(tiny_root, seed):
    """At the tiny bf16 cell: the program (bf16 on the CPU) is within the
    cell's limits, the reference with float8 matrix operands is not."""
    cell = spec.load_cell("tiny-bf16-lognormal", tiny_root)
    prog = harness.Program(cell)
    prog.start(seed)
    mine = prog.warm_up(seed, 3)
    prog.free()
    with jax.default_matmul_precision("highest"):
        ref = reference.reference_rounds(cell.shape, cell.traffic, seed, seed, 3)
        ctl = reference.reference_rounds(cell.shape, cell.traffic, seed, seed, 3,
                                         quant=reference.fp8_quant)
    limits = {**cell.limits}
    assert compare.all_within(compare.checks(compare.numbers(mine, ref, []), limits))
    assert not compare.all_within(compare.checks(compare.numbers(ctl, ref, []), limits))


def test_traffic_outside_the_reference_is_refused(tiny_root):
    cell = spec.load_cell("tiny-lognormal", tiny_root)
    bad = {**cell.traffic, "fl": {**cell.traffic["fl"], "algorithm": "fedavg"}}
    with pytest.raises(NotImplementedError):
        reference.round_plan(bad, 256, 1, 0)


def test_seed_words_cover_64_bits():
    assert weights.seed_words(2 ** 33 + 5) == (5, 2)
    with pytest.raises(ValueError):
        weights.seed_words(-1)
