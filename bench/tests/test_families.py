"""Model families as files: the dense family's weights are the ones the
benchmark always made, and a family the shared code has never seen (the
test-only ``hetero`` under the tiny root) is taken by ``spec``, ``weights``,
``reference``, ``flops`` and ``harness.arch_config`` as it stands."""
import hashlib

import jax
import numpy as np

from bench import flops, harness, reference, spec, weights


def digest(tree) -> str:
    h = hashlib.sha256()
    for k, v in sorted(weights.flatten(tree).items()):
        a = np.asarray(v)
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_dense_weights_are_unchanged(tiny_root):
    """Pinned from the weights the benchmark made before its model code
    moved into ``bench/families/dense.py``."""
    s = spec.load_cell("tiny-lognormal", tiny_root).shape
    assert digest(weights.init_params(s, 0)) == (
        "2d86ecfd6b373738adf685f0036ba76da61fdcbad329c6a00ce9c27f271ea4bf")


def test_a_new_family_is_new_files_only(tiny_root):
    cell = spec.load_cell("tiny-hetero", tiny_root)
    s = cell.shape
    assert spec.family_of(s) is cell.family is spec.family("hetero", tiny_root)
    assert type(s).__name__ == "Shape" and s.d_ff_first == 192 and hash(s) == hash(cell.shape)

    leaves = cell.family.leaf_shapes(s)
    x = weights.init_params(s, 5)
    flat = weights.flatten(x)
    assert {k: (v.shape, v.dtype) for k, v in flat.items()} == {
        k: (shape, np.dtype("float32")) for k, (shape, _) in leaves.items()}
    assert flat["first/mlp/gate"].shape == (1, 64, 192) and "lm_head" in flat
    assert all(v == 0.0 for v in weights.change_norms(s, x, 5).values())

    n = sum(int(np.prod(shape)) for shape, _ in leaves.values())
    assert flops.param_count(s) == n
    assert flops.train_flops_per_token(s, 16) == 6.0 * (n - 256 * 64)

    arch = harness.arch_config(s, "tiny-hetero")
    assert (arch.name, arch.n_layers, arch.d_model, arch.tie_embeddings) == (
        "tiny-hetero", 3, 64, False)

    with jax.default_matmul_precision("highest"):
        r = reference.reference_rounds(s, cell.traffic, 5, 5, 2)
    assert len(r.losses) == 2 and all(np.isfinite(r.losses))
    assert set(r.change_norms) == set(leaves)
    # every layer of both stacks trained
    assert all(r.change_norms[k] > 0 for k in leaves if "/mlp/" in k)


def test_dense_shape_keeps_its_older_name():
    assert spec.ModelShape is spec.family("dense").Shape
