"""Robustness-plane units + breakdown-point property tests.

Covers the three robust layers in isolation: adversary draws (counter-based,
backend-equal, round-independent), attack models over hand-built delta
stacks, the robust aggregators' breakdown-point contracts (a weighted
location estimate x total coefficient mass, immune to adversarial mass
below the estimator's breakdown point), and the quarantine / reject guard
primitives.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import assume, given, settings
from hypothesis import strategies as st

from repro.configs.base import FLConfig
from repro.data.federated import ClientMeta
from repro.fed.robust import (ATTACKS, GUARDS, ROBUST_AGGS, adversary_mask,
                              build_attack, build_robust_aggregate,
                              register_attack, register_robust_agg,
                              robust_active, validate_robust_config)
from repro.fed.robust.attacks import attack_round_keys
from repro.fed.robust.guards import (GROWTH_LIMIT, SPIKE_MULT, params_ok,
                                     quarantine_masks, renormalize_coeffs,
                                     select_state, suspicion_ratio)


def _fl(**kw):
    kw.setdefault("num_clients", 8)
    kw.setdefault("cohort_size", 4)
    kw.setdefault("sampling", "uniform")
    kw.setdefault("epochs", 1)
    kw.setdefault("local_batch", 2)
    return FLConfig(**kw)


def _meta(valid, ids=None):
    valid = jnp.asarray(valid, jnp.float32)
    C = valid.shape[0]
    ids = jnp.arange(C, dtype=jnp.int32) if ids is None else jnp.asarray(ids)
    one = jnp.ones(C, jnp.float32)
    return ClientMeta(weight=one / C, prob=one, num_samples=one, epochs=one,
                      num_steps=one, num_steps_planned=one, valid=valid,
                      client_id=ids)


def _stack(values):
    """A one-leaf [C, 2] delta tree where each client ships a constant."""
    v = jnp.asarray(values, jnp.float32)
    return {"x": jnp.stack([v, v], axis=1)}


def _agg(name, deltas, coeff, meta, **fl_kw):
    fl = _fl(aggregator=name, **fl_kw)
    return build_robust_aggregate(fl)(deltas, jnp.asarray(coeff, jnp.float32),
                                      meta)


# ---------------------------------------------------------------------------
# adversary draws
# ---------------------------------------------------------------------------


def test_adversary_mask_backend_and_replay():
    ids = np.arange(64, dtype=np.uint32)
    m_np = adversary_mask(7, ids, 0.3, xp=np)
    m_j = adversary_mask(7, jnp.asarray(ids), 0.3)
    np.testing.assert_array_equal(m_np, np.asarray(m_j))   # numpy == jnp
    np.testing.assert_array_equal(m_np, adversary_mask(7, ids, 0.3, xp=np))
    assert set(np.unique(m_np)) <= {0.0, 1.0}
    # membership is a pure per-id function: any cohort sees the same subset
    sub = np.array([3, 17, 42], np.uint32)
    np.testing.assert_array_equal(adversary_mask(7, sub, 0.3, xp=np),
                                  m_np[[3, 17, 42]])
    # monotone in frac; empty and (almost-)full extremes
    assert adversary_mask(7, ids, 0.0, xp=np).sum() == 0
    wider = adversary_mask(7, ids, 0.9, xp=np)
    assert np.all(wider >= m_np) and wider.sum() > m_np.sum()
    # different seeds draw different sets
    assert not np.array_equal(m_np, adversary_mask(8, ids, 0.3, xp=np))


def test_attack_round_keys_vary_by_round_not_backend():
    ids = np.arange(8, dtype=np.uint32)
    k0 = attack_round_keys(3, ids, np.uint32(0), xp=np)
    k1 = attack_round_keys(3, ids, np.uint32(1), xp=np)
    assert not np.array_equal(k0, k1)
    np.testing.assert_array_equal(
        k0, np.asarray(attack_round_keys(3, jnp.asarray(ids), jnp.uint32(0))))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), frac=st.floats(0.05, 0.95))
def test_adversary_mask_frequency(seed, frac):
    ids = np.arange(2048, dtype=np.uint32)
    rate = adversary_mask(seed, ids, frac, xp=np).mean()
    assert abs(rate - frac) < 0.08                       # ~4 sigma at n=2048


# ---------------------------------------------------------------------------
# attacks over a hand-built stack
# ---------------------------------------------------------------------------


def _apply(name, values, adv, scale=1.0, frac=0.5, seed=0):
    fl = _fl(attack=name, attack_frac=frac, attack_scale=scale, seed=seed)
    deltas = _stack(values)
    adv = jnp.asarray(adv, jnp.float32)
    meta = _meta(np.ones(len(values)))
    keys = attack_round_keys(fl.seed, meta.client_id, jnp.uint32(0))
    return np.asarray(ATTACKS[name](deltas, adv, meta, keys, fl)["x"])


def test_sign_flip_and_zero_update():
    vals, adv = [1.0, 2.0, 3.0, 4.0], [0, 1, 0, 1]
    out = _apply("sign_flip", vals, adv, scale=2.0)
    np.testing.assert_allclose(out[:, 0], [1.0, -4.0, 3.0, -8.0])
    out = _apply("zero_update", vals, adv)
    np.testing.assert_allclose(out[:, 0], [1.0, 0.0, 3.0, 0.0])


def test_scaled_noise_is_bounded_and_round_keyed():
    fl = _fl(attack="scaled_noise", attack_frac=0.5, attack_scale=3.0, seed=1)
    deltas = _stack([0.0] * 6)
    meta = _meta(np.ones(6))
    adv = jnp.ones(6, jnp.float32)
    k0 = attack_round_keys(fl.seed, meta.client_id, jnp.uint32(0))
    k1 = attack_round_keys(fl.seed, meta.client_id, jnp.uint32(1))
    n0 = np.asarray(ATTACKS["scaled_noise"](deltas, adv, meta, k0, fl)["x"])
    n1 = np.asarray(ATTACKS["scaled_noise"](deltas, adv, meta, k1, fl)["x"])
    assert np.all(np.abs(n0) <= 3.0) and np.all(np.abs(n1) <= 3.0)
    assert not np.array_equal(n0, n1)                    # per-round stream
    n0b = np.asarray(ATTACKS["scaled_noise"](deltas, adv, meta, k0, fl)["x"])
    np.testing.assert_array_equal(n0, n0b)               # replayable


def test_ipm_ships_negated_honest_mean():
    vals, adv = [1.0, 3.0, 100.0], [0, 0, 1]
    out = _apply("ipm", vals, adv, scale=0.5)
    np.testing.assert_allclose(out[0, 0], 1.0)           # honest untouched
    np.testing.assert_allclose(out[2, 0], -0.5 * 2.0)    # -scale * mean(1, 3)


def test_build_attack_none_and_unknown():
    assert build_attack(_fl()) is None
    with pytest.raises(ValueError, match="unknown attack"):
        build_attack(_fl(attack="bogus"))


# ---------------------------------------------------------------------------
# aggregator breakdown-point properties
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(v=st.floats(-5.0, 5.0), bad=st.floats(50.0, 1e4),
       n_honest=st.integers(3, 10), n_adv=st.integers(1, 3),
       low_side=st.booleans())
def test_median_recovers_honest_value_under_minority(v, bad, n_honest, n_adv,
                                                     low_side):
    """All honest clients ship v; adversaries (< half the coefficient mass)
    ship an arbitrary outlier — the weighted median must return v * W."""
    if n_adv * 2 >= n_honest + n_adv:
        n_adv = (n_honest - 1) // 2
    vals = [v] * n_honest + [(-bad if low_side else bad)] * n_adv
    coeff = np.ones(len(vals), np.float32)
    out = _agg("coordinate_median", _stack(vals), coeff, _meta(np.ones(len(vals))))
    W = coeff.sum()
    np.testing.assert_allclose(np.asarray(out["x"]), v * W, rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(v=st.floats(-5.0, 5.0), bad=st.floats(100.0, 1e4),
       n=st.integers(6, 12), trim=st.floats(0.15, 0.4),
       low_side=st.booleans())
def test_trimmed_mean_recovers_honest_value_below_trim(v, bad, n, trim,
                                                       low_side):
    """Adversarial coefficient mass strictly below trim_frac * W lands
    entirely outside the central window — the estimate is exactly v * W."""
    # the aggregator's window [lo, hi] in its own float32 arithmetic; the
    # most adversaries whose mass stays strictly below lo, and at least one
    lo, hi = np.float32(trim) * np.float32(n), np.float32(1.0 - trim) * np.float32(n)
    n_adv = int(np.ceil(lo)) - 1
    assume(n_adv >= 1 and n - n_adv > hi)
    vals = [v] * (n - n_adv) + [(-bad if low_side else bad)] * n_adv
    coeff = np.ones(n, np.float32)
    out = _agg("trimmed_mean", _stack(vals), coeff, _meta(np.ones(n)),
               trim_frac=trim)
    np.testing.assert_allclose(np.asarray(out["x"]), v * n, rtol=1e-4,
                               atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(v=st.floats(-3.0, 3.0), spread=st.floats(0.0, 0.1),
       bad=st.floats(50.0, 1e4), n_honest=st.integers(5, 10),
       n_adv=st.integers(1, 2))
def test_krum_selects_an_honest_client(v, spread, bad, n_honest, n_adv):
    """Honest clients cluster around v, adversaries sit far away and
    mutually apart: Krum's k-nearest scoring must pick a cluster member
    (requires |valid| >= 2f + 3, satisfied by construction here)."""
    rng = np.random.default_rng(0)
    honest = v + spread * rng.standard_normal(n_honest)
    adv = [bad * (i + 1) for i in range(n_adv)]          # mutually far apart
    vals = list(honest) + list(adv)
    n = len(vals)
    coeff = np.ones(n, np.float32)
    out = _agg("krum", _stack(vals), coeff, _meta(np.ones(n)), trim_frac=0.25)
    got = np.asarray(out["x"])[0] / n                    # undo the W scale
    assert np.min(np.abs(got - honest)) < 1e-5           # an honest value
    mk = _agg("multi_krum", _stack(vals), coeff, _meta(np.ones(n)),
              trim_frac=0.25)
    got_mk = np.asarray(mk["x"])[0] / n
    assert honest.min() - 1e-4 <= got_mk <= honest.max() + 1e-4


def test_mean_is_canonical_weighted_sum():
    from repro.fed.strategy import weighted_sum

    rng = np.random.default_rng(1)
    deltas = {"a": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal((5, 2, 2)), jnp.float32)}
    coeff = jnp.asarray(rng.uniform(0, 2, 5), jnp.float32)
    out = _agg("mean", deltas, coeff, _meta(np.ones(5)))
    ref = weighted_sum(deltas, coeff)
    for k in deltas:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))


def test_aggregators_respect_zero_coefficient_slots():
    """Invalid / quarantined slots (coeff 0) must never influence any
    estimator, however huge their (finite) garbage — the non-finite case is
    the quarantine scrub's job (``scrub_deltas``), tested below."""
    vals = [1.0, 1.0, 1.0, 1e8]
    coeff = np.array([1.0, 1.0, 1.0, 0.0], np.float32)
    meta = _meta([1, 1, 1, 0])
    for name in ("mean", "coordinate_median", "trimmed_mean", "norm_clip",
                 "centered_clip", "krum", "multi_krum"):
        out = _agg(name, _stack(vals), coeff, meta, trim_frac=0.2)
        np.testing.assert_allclose(np.asarray(out["x"]), 3.0, rtol=1e-5,
                                   err_msg=name)


def test_scrub_then_aggregate_neutralizes_nonfinite():
    """The quarantine pipeline end-to-end: a NaN client is masked, its
    coefficient mass redistributed, its values scrubbed — every estimator
    then returns the honest aggregate (0 * nan = nan makes the scrub
    load-bearing, not cosmetic)."""
    from repro.fed.robust import scrub_deltas

    vals = [1.0, 1.0, 1.0, np.nan]
    deltas, meta = _stack(vals), _meta(np.ones(4))
    healthy, _ = quarantine_masks(deltas, meta)
    np.testing.assert_array_equal(np.asarray(healthy), [1, 1, 1, 0])
    coeff = renormalize_coeffs(jnp.ones(4, jnp.float32), healthy)
    scrubbed = scrub_deltas(deltas, healthy)
    assert np.all(np.isfinite(np.asarray(scrubbed["x"])))
    for name in ROBUST_AGGS:
        out = _agg(name, scrubbed, coeff, meta, trim_frac=0.2)
        np.testing.assert_allclose(np.asarray(out["x"]), 4.0, rtol=1e-5,
                                   err_msg=name)  # renormalized W = 4


def test_norm_clip_bounds_outlier_influence():
    vals = [1.0, 1.0, 1.0, 1000.0]
    coeff = np.ones(4, np.float32)
    out = _agg("norm_clip", _stack(vals), coeff, _meta(np.ones(4)))
    # the outlier is clipped to the median norm (=|1|), not removed:
    # aggregate <= 4 honest-sized contributions x W-scale
    assert np.all(np.asarray(out["x"]) <= 4.0 + 1e-4)


def test_centered_clip_tracks_honest_center():
    vals = [2.0, 2.0, 2.0, 2.0, 1e4]
    coeff = np.ones(5, np.float32)
    out = _agg("centered_clip", _stack(vals), coeff, _meta(np.ones(5)))
    est = np.asarray(out["x"])[0] / 5.0                  # location estimate
    assert abs(est - 2.0) < 1.0                          # outlier influence bounded


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------


def test_quarantine_flags_nonfinite_and_spikes():
    vals = [1.0, 1.1, 0.9, 100.0, np.nan]
    deltas = _stack(vals)
    meta = _meta(np.ones(5))
    healthy, suspected = quarantine_masks(deltas, meta)
    np.testing.assert_array_equal(np.asarray(healthy), [1, 1, 1, 0, 0])
    # the spike is "suspected adversary"; the NaN is sick, not suspicious
    np.testing.assert_array_equal(np.asarray(suspected), [0, 0, 0, 1, 0])
    ratio = np.asarray(suspicion_ratio(deltas, meta))
    assert ratio[3] > SPIKE_MULT and ratio[4] == 1e9
    assert np.all(ratio[:3] < SPIKE_MULT)


@settings(max_examples=25, deadline=None)
@given(coeffs=st.lists(st.floats(0.01, 5.0), min_size=2, max_size=12),
       drop=st.integers(0, 10))
def test_renormalize_preserves_total_mass(coeffs, drop):
    cf = np.asarray(coeffs, np.float32)
    healthy = np.ones(len(cf), np.float32)
    healthy[: min(drop, len(cf) - 1)] = 0.0              # keep >= 1 survivor
    out = np.asarray(renormalize_coeffs(jnp.asarray(cf), jnp.asarray(healthy)))
    np.testing.assert_allclose(out.sum(), cf.sum(), rtol=1e-5)
    assert np.all(out[healthy == 0] == 0.0)


def test_renormalize_all_quarantined_degrades_to_zero():
    cf = jnp.ones(4, jnp.float32)
    out = np.asarray(renormalize_coeffs(cf, jnp.zeros(4, jnp.float32)))
    np.testing.assert_array_equal(out, np.zeros(4))      # no-op round


def test_params_ok_and_select_state():
    from repro.fed.server import ServerState

    prev = ServerState(params={"x": jnp.ones(3)}, opt={"m": jnp.zeros(3)},
                       rnd=jnp.asarray(4, jnp.int32))
    good = ServerState(params={"x": jnp.full(3, 2.0)},
                       opt={"m": jnp.full(3, 0.5)}, rnd=jnp.asarray(5, jnp.int32))
    blown = ServerState(params={"x": jnp.full(3, GROWTH_LIMIT * 10)},
                        opt=good.opt, rnd=good.rnd)
    naned = ServerState(params={"x": jnp.array([1.0, jnp.nan, 1.0])},
                        opt=good.opt, rnd=good.rnd)
    assert bool(params_ok(prev.params, good.params))
    assert not bool(params_ok(prev.params, blown.params))
    assert not bool(params_ok(prev.params, naned.params))
    kept = select_state(params_ok(prev.params, blown.params), blown, prev)
    np.testing.assert_array_equal(np.asarray(kept.params["x"]), np.ones(3))
    np.testing.assert_array_equal(np.asarray(kept.opt["m"]), np.zeros(3))
    assert int(kept.rnd) == 5                            # rnd always advances
    took = select_state(params_ok(prev.params, good.params), good, prev)
    np.testing.assert_array_equal(np.asarray(took.params["x"]), np.full(3, 2.0))


# ---------------------------------------------------------------------------
# config surface + registries
# ---------------------------------------------------------------------------


def test_robust_active_and_validate():
    assert not robust_active(_fl())
    assert robust_active(_fl(attack="sign_flip", attack_frac=0.2))
    assert robust_active(_fl(aggregator="krum"))
    assert robust_active(_fl(guard="full"))
    validate_robust_config(_fl(attack="ipm", attack_frac=0.3,
                               aggregator="trimmed_mean", trim_frac=0.35,
                               guard="full"))
    for bad in (_fl(attack="bogus", attack_frac=0.2),
                _fl(attack="sign_flip", attack_frac=0.0),
                _fl(attack="sign_flip", attack_frac=1.5),
                _fl(attack="sign_flip", attack_frac=0.2, attack_scale=0.0),
                _fl(aggregator="bogus"),
                _fl(aggregator="trimmed_mean", trim_frac=0.0),
                _fl(aggregator="krum", trim_frac=0.5),
                _fl(guard="bogus")):
        with pytest.raises(ValueError):
            validate_robust_config(bad)
    assert "off" in GUARDS and "mean" in ROBUST_AGGS and "ipm" in ATTACKS


def test_bind_strategy_validates_robust():
    from repro.fed.losses import make_quadratic_loss
    from repro.fed.strategy import bind_strategy, strategy_for

    fl = _fl(aggregator="trimmed_mean", trim_frac=0.9, algorithm="fedavg",
             local_lr=0.1)
    with pytest.raises(ValueError, match="trim_frac"):
        bind_strategy(strategy_for(fl), fl, make_quadratic_loss(3),
                      num_clients=fl.num_clients)


def test_robust_registrars_refuse_duplicates():
    with pytest.raises(ValueError, match="overwrite=True"):
        register_attack("sign_flip", object())
    with pytest.raises(ValueError, match="overwrite=True"):
        register_robust_agg("mean", object())
    register_attack("sign_flip", ATTACKS["sign_flip"], overwrite=True)
    register_robust_agg("mean", ROBUST_AGGS["mean"], overwrite=True)
