"""A client run alone stops at its last unmasked step.

``build_local_step(..., trim_padding=True)`` (what a sequential cohort binds)
runs each client's local loop with a traced trip count instead of the fixed
``K_max``-step scan: bitwise the same results for every mask shape, the
padded tail never computed (a NaN-gradient padding batch cannot reach the
iterate), vmapped cohorts still compiling the fixed scan, and the
``data/local_steps`` counter saying how many steps that saves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.local import ClientChain, build_local_step, resolve_chain
from repro.data.federated import (Bucket, BucketedBatch, ClientMeta,
                                  FederatedPipeline, Population, RoundBatch)
from repro.data.tasks import DuplicatedQuadraticTask
from repro.fed.losses import make_quadratic_loss
from repro.fed.rounds import as_device_batch, build_round_step, local_steps
from repro.fed.strategy import bind_strategy, strategy_for
from repro.obs import trace

DIM, K_MAX, B = 3, 4, 2
LOSS = make_quadratic_loss(DIM)
CHAINS = {"sgd": (), "mvr": ("mvr",), "scaffold": ("scaffold",)}
MASKS = {
    "trailing_padding": [1, 1, 0, 0],
    "hole_before_last": [1, 0, 1, 0],
    "all_masked": [0, 0, 0, 0],
    "full": [1, 1, 1, 1],
}


def _tree(seed, scale=1.0):
    return {"x": scale * jax.random.normal(jax.random.PRNGKey(seed), (DIM,), jnp.float32)}


def _inputs(chain):
    """(one_client args but data and mask) for a chain: server momentum for
    mvr, the server and client control variates for scaffold."""
    params, momentum = _tree(0), _tree(1, 0.3)
    opt, cstate = {}, {}
    if chain == "scaffold":
        opt = {"c": _tree(2, 0.1)}
        cstate = {"scaffold": {"c": _tree(3, 0.1)}}
    return params, momentum, opt, cstate


def _one_client(chain, trim):
    fl = FLConfig(mvr_a=0.2)
    transforms = resolve_chain(ClientChain(chain, CHAINS[chain]), LOSS, fl)
    return jax.jit(build_local_step(transforms, LOSS, trim_padding=trim))


def _data(seed=4):
    return {"e": jax.random.normal(jax.random.PRNGKey(seed), (K_MAX, B, DIM), jnp.float32)}


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_trimmed_loop_is_bitwise_fixed_scan(chain, mask):
    params, momentum, opt, cstate = _inputs(chain)
    step_mask = jnp.asarray(MASKS[mask], jnp.float32)
    eta = jnp.float32(0.05)
    args = (params, momentum, opt, _data(), step_mask, eta, cstate)
    _assert_bitwise(_one_client(chain, True)(*args), _one_client(chain, False)(*args))


@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_padding_is_not_computed(chain):
    """Padding batches whose loss and gradient are NaN: the fixed scan's
    masked descent turns the iterate into NaN (0 * NaN); the trimmed loop
    never reaches them and equals the run on the unpadded data bitwise."""
    params, momentum, opt, cstate = _inputs(chain)
    eta = jnp.float32(0.05)
    mask = jnp.asarray(MASKS["trailing_padding"], jnp.float32)
    data = _data()
    poisoned = {"e": data["e"].at[2:].set(jnp.nan)}
    fixed = _one_client(chain, False)(params, momentum, opt, poisoned, mask, eta, cstate)
    assert np.isnan(np.asarray(fixed[0]["x"])).all()
    got = _one_client(chain, True)(params, momentum, opt, poisoned, mask, eta, cstate)
    want = _one_client(chain, True)(params, momentum, opt, {"e": data["e"][:2]},
                                    mask[:2], eta, cstate)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(got))
    _assert_bitwise(got, want)


def _primitives(jaxpr, out):
    """(primitive name, scan length or None) of every equation, nested
    jaxprs (scan and while bodies, calls) included."""
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, eqn.params.get("length")))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("mode", ["vmapped", "sequential"])
def test_cohort_mode_picks_the_local_loop(mode):
    """A vmapped cohort keeps the fixed K_max-step scan and no while loop; a
    sequential one runs its clients' local steps in a while loop and scans
    only over the cohort."""
    task = DuplicatedQuadraticTask(copies=(1, 2, 3))
    fl = FLConfig(num_clients=3, cohort_size=2, sampling="uniform", epochs=2,
                  local_batch=1, algorithm="fedshuffle", local_lr=0.05,
                  server_lr=0.8, seed=11, cohort_mode=mode)
    pipe = FederatedPipeline(task, Population.build(fl, sizes=task.sizes()), fl)
    batch = as_device_batch(pipe.round_batch(0))
    k_max = batch.step_mask.shape[1]
    assert k_max != fl.cohort_size
    strat = bind_strategy(strategy_for(fl), fl, make_quadratic_loss(task.dim),
                          num_clients=fl.num_clients)
    step = build_round_step(strat.loss_fn, strat, fl)
    state = strat.init({"x": jnp.zeros((task.dim,), jnp.float32)})
    prims = _primitives(jax.make_jaxpr(step)(state, batch).jaxpr, [])
    scans = {n for p, n in prims if p == "scan"}
    has_while = any(p == "while" for p, _ in prims)
    if mode == "vmapped":
        assert k_max in scans and not has_while
    else:
        assert has_while and scans == {fl.cohort_size}


def _meta(c):
    z = np.zeros(c, np.float32)
    return ClientMeta(weight=z, prob=z, num_samples=z, epochs=z, num_steps=z,
                      num_steps_planned=z, valid=z, client_id=np.arange(c))


def test_local_steps_counter_round_batch():
    mask = np.array([[1, 1, 0, 0, 0],
                     [1, 0, 1, 1, 0],
                     [0, 0, 0, 0, 0],
                     [1, 1, 1, 1, 1]], np.float32)
    rb = RoundBatch(data={"e": np.zeros((4, 5, 1, DIM), np.float32)},
                    step_mask=mask, meta=_meta(4))
    assert local_steps(rb) == (20, 2 + 4 + 0 + 5)
    with trace.capture() as tr:
        as_device_batch(rb)
    assert [(e["name"], e["args"]) for e in tr.events if e["ph"] == "C"] == [
        ("data/local_steps", {"laid_out": 20, "computed": 11})]


def test_local_steps_counter_bucketed_batch():
    def bucket(mask):
        mask = np.asarray(mask, np.float32)
        return Bucket(data={"e": np.zeros(mask.shape + (1, DIM), np.float32)},
                      idx=None, step_mask=mask, slots=np.arange(len(mask)))

    bb = BucketedBatch(buckets=(bucket([[1, 0], [1, 1]]),
                                bucket([[1, 1, 1, 0, 0, 0]])),
                       meta=_meta(3), pos=np.arange(3))
    assert local_steps(bb) == (4 + 6, 1 + 2 + 3)
