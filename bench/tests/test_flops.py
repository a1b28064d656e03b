"""Parameter and FLOP counts of the benchmark's configurations."""
import pytest

from bench import flops, spec


@pytest.mark.parametrize("cell,params,gflop", [
    ("qwen05b-xdev-lognormal", 463987712, 2.934921216),
    ("minicpm2b-xdev-equal", 1259647488, 7.784377344),
])
def test_counts_match_the_configuration(cell, params, gflop):
    c = spec.load_cell(cell)
    assert flops.param_count(c.shape) == params == c.config["params"]
    s = c.shape
    attn = 12 * s.layers * s.heads * s.head_dim * 512
    assert flops.train_flops_per_token(s, 512) == 6 * params + attn
    assert abs(flops.train_flops_per_token(s, 512) / 1e9 - gflop) < 1e-9


def test_untied_head_counts_the_output_product_once():
    c = spec.load_cell("qwen05b-xdev-equal")
    untied = spec.ModelShape(**{**c.shape.__dict__, "tied": False})
    extra = untied.vocab * untied.d_model
    assert flops.param_count(untied) == flops.param_count(c.shape) + extra
    assert flops.train_flops_per_token(untied, 512) == flops.train_flops_per_token(c.shape, 512)
