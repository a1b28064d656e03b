"""Direction-aware codecs — the compressed communication plane's algorithm
layer, covering BOTH directions of the cross-device wire.

FedShuffle targets the cross-device regime where communication is the
bottleneck: every round each sampled client ships its model update
``Delta_i = y_i - x`` back to the server (the **uplink**), and the server
broadcasts the new model to the next cohort (the **downlink**).  Sadiev et
al. 2022 (Q-RR / Q-NASTYA / DIANA-RR) show random reshuffling composes with
quantized / sparsified communication in both directions, which is exactly
what this module implements: a :class:`Codec` is the per-client
``encode -> wire -> decode`` rule the round driver applies *inside the
jitted round*, on slot-order ``[C]`` arrays — aggregation always combines
the **decoded** updates, so the math is identical between the padded and
bucketed execution layouts.

Directions
----------
Every :data:`CODECS` entry registers with a declared direction capability —
``"uplink"``, ``"downlink"`` or ``"both"`` — and each direction resolves its
own ``FLConfig`` knob family (``uplink*`` / ``downlink*``) through the
shared per-direction validator :func:`validate_codec_knobs`:

* **uplink** (``fl.uplink``): each client compresses its update
  (:func:`uplink_apply`); the server decodes-then-combines.  Optional
  per-client compressor state (error-feedback residuals, DIANA shifts)
  rides the ``[N+1, ...]`` bank on ``ServerState.clients`` under the
  reserved key ``"uplink"``.
* **downlink** (``fl.downlink``): the server compresses the model's delta
  against a *client-held reference* (:func:`downlink_apply`) — the
  reference rides the bank under the reserved key ``"downlink"`` — and the
  client's reconstruction ``ref + decode(encode(x - ref))`` becomes both
  its round-start point and its next reference.  Server and client stay in
  exact agreement about what the client holds even under partial
  participation (a skipped client's reference goes stale; it never
  desyncs).  Downlink-capable codecs are the **stateless** ones:
  client-side compressor state cannot ride the server's broadcast, and
  :func:`register_codec` rejects the conflict at registration time.

Protocol (mirrors the ClientTransform design in ``repro.core.local``):

* ``encode(leaf, key) -> payload`` / ``decode(payload, key, like) -> leaf``
  run per *leaf* of one payload (the tree-level harness derives per-leaf
  subkeys).  The payload pytree IS the wire format — ``wire_bits(like)``
  charges exactly its bytes (:func:`wire_bits_total` sums a whole tree).
* optional **per-client uplink state**: ``client_init(params)`` declares one
  client's state template (EF residual ``e``, DIANA shift ``h``), banked
  ``[N+1, ...]`` on ``ServerState.clients`` — gathered O(cohort) per round,
  slot-order scattered back, checkpointed/resumed bitwise by
  ``save_server_state`` like any other client state.  ``apply`` (tree-level,
  optional) overrides the whole per-client hook for compositions the EF
  recipe cannot express (DIANA's shifted compression).
* ``seeded`` marks codecs whose randomness (stochastic rounding, random
  coordinate choice) must be keyed: the driver derives one uint32 key per
  (seed, client, round) via :func:`round_keys` — the downlink folds in an
  extra subtag (:func:`downlink_round_keys`) so the two directions' streams
  never correlate — and every stream is stateless, reproducible, and
  identical across the legacy / engine / prefetch paths and across
  checkpoint resume.

Built-ins (:data:`CODECS`; ``FLConfig.uplink`` / ``FLConfig.downlink``):

=========== ========== =====================================================
name        direction
=========== ========== =====================================================
identity    both       exact pass-through (the default; bitwise-frozen)
qsgd        both       stochastic int quantization, per-chunk fp32 scales
                       (``*_bits``/``*_chunk``; ``kernels.quantize`` packs)
topk        uplink     magnitude top-k sparsification + error feedback
                       (``uplink_frac``; values + int32 indices on the wire)
randk       both       seeded random-k sparsification, unbiased n/k scaling
                       (indices regenerated from the key — values-only wire)
ef_qsgd     uplink     qsgd + error feedback
ef_randk    uplink     randk + error feedback
diana_qsgd  uplink     qsgd through DIANA learned shifts (``shift_alpha``)
diana_randk uplink     randk through DIANA learned shifts
diana_topk  uplink     top-k + error feedback + DIANA learned shifts
=========== ========== =====================================================

Robustness-plane ordering: the round driver applies client attacks
(``fl.attack``, ``repro.fed.robust``) *before* uplink ``encode`` — a
Byzantine client controls the payload it ships, so the attack corrupts what
goes on the wire and the codec faithfully compresses the corrupted update.
Robust aggregators and quarantine guards then operate on the **decoded**
deltas, the same arrays honest aggregation would see.
"""
from __future__ import annotations

import inspect
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ...configs.base import FLConfig
from ...kernels.quantize.ops import quantize_pack, unpack_dequantize
from ...kernels.quantize.ref import BITS_CHOICES, packed_width
from ...kernels.rr_perm.ref import key_combine, stream_key, swap_or_not
from ...utils.pytree import tree_zeros_like
from ...utils.tags import SUB_COMM_DOWNLINK, TAG_COMM

# ServerState.clients keys the comm plane's per-client banks live under —
# reserved: bind_strategy refuses local chains with a stateful transform of
# either name.  "uplink" holds compressor state (EF residual e / DIANA shift
# h); "downlink" holds the broadcast reference {"ref": params-shaped}.
UPLINK_STATE_KEY = "uplink"
DOWNLINK_STATE_KEY = "downlink"

# the FLConfig knob families, one per direction (fl.<direction>,
# fl.<direction>_bits / _chunk / _frac)
DIRECTIONS = ("uplink", "downlink")

_TAG_COMM = TAG_COMM     # domain-separates comm streams (registry: utils/tags.py)
_SUB_DOWNLINK = SUB_COMM_DOWNLINK


def round_keys(seed: int, client_id, rnd, xp=jnp):
    """Per-client uplink stream keys for one round ([C] uint32).

    Same (seed, client, round) chain as the RR index streams
    (``kernels.rr_perm.ref.stream_key``) with a comm tag folded in, so the
    codec randomness is domain-separated from the reshuffling randomness but
    shares its reproducibility story: stateless, identical wherever the
    round is produced (legacy host path, cohort engine, prefetch thread,
    checkpoint resume)."""
    dt = xp.uint32
    base = stream_key(seed, xp.asarray(client_id).astype(dt),
                      xp.asarray(rnd).astype(dt), xp)
    return key_combine(base, dt(_TAG_COMM), xp)


def downlink_round_keys(seed: int, client_id, rnd, xp=jnp):
    """Per-client downlink stream keys for one round ([C] uint32).

    The uplink chain with the downlink subtag folded in: a round where both
    directions compress draws two independent streams per (seed, client,
    round), so the server's stochastic rounding never correlates with the
    client's — while keeping the same statelessness guarantees."""
    return key_combine(round_keys(seed, client_id, rnd, xp),
                       xp.uint32(_SUB_DOWNLINK), xp)


class Codec(NamedTuple):
    """One compression rule (all hooks pure pytree functions).

    ``encode``/``decode``/``wire_bits`` are leaf-level (the harness maps
    them over the payload tree with per-leaf subkeys); ``client_init``/
    ``finalize``/``apply`` are tree-level (uplink-only — compressor state
    mirrors the params tree and lives on the client).
    ``decode(payload, key, like)`` must return ``like.shape``/``like.dtype``;
    ``wire_bits(like)`` is static accounting — a python number of bits one
    endpoint pays to ship this leaf.  ``direction`` declares which wire
    directions the rule can serve (``"uplink"`` / ``"downlink"`` /
    ``"both"``); any codec keeping client state is uplink-only.
    """

    name: str
    encode: Callable                       # (leaf, key) -> payload dict
    decode: Callable                       # (payload, key, like) -> leaf
    wire_bits: Callable                    # (like) -> bits (python number)
    client_init: Callable | None = None    # (params) -> uplink state pytree
    finalize: Callable | None = None       # (src, dhat, state) -> state'
    seeded: bool = False
    apply: Callable | None = None          # tree-level override (DIANA):
    #                                        (roundtrip, delta, state, key)
    #                                        -> (delta_hat, state')
    direction: str = "both"                # declared direction capability


def with_error_feedback(inner: Codec, *, name: str | None = None) -> Codec:
    """Wrap a codec with the EF-SGD residual loop: the client compresses
    ``Delta + e`` and keeps ``e' = (Delta + e) - decoded`` in its bank row,
    so whatever the compressor drops this round is retransmitted later —
    the standard fix for biased compressors (top-k) and a variance help for
    unbiased ones.  Wire format and accounting are the inner codec's.  The
    residual lives on the client, so the composition is uplink-only."""
    if inner.client_init is not None:
        raise ValueError(f"codec {inner.name!r} already keeps per-client state")
    return inner._replace(
        name=name or f"ef_{inner.name}",
        client_init=lambda params: {"e": tree_zeros_like(params)},
        direction="uplink",
    )


def with_diana_shift(inner: Codec, alpha: float, *,
                     name: str | None = None) -> Codec:
    """Wrap a codec with DIANA-RR learned shifts (Sadiev et al. 2022): each
    client keeps a shift ``h_i`` next to any EF residual, ships
    ``C(Delta_i - h_i)``, the server reconstructs ``h_i + C(Delta_i - h_i)``
    and BOTH ends apply ``h_i <- h_i + alpha * C(Delta_i - h_i)`` — the
    compressor only ever sees the drift off the learned shift, which shrinks
    as training stabilizes.  Composes with error feedback (wrap the EF codec;
    the compressed source is then ``Delta + e - h``) and the shift bank rides
    the ``"uplink"`` state key like the residual.  Uplink-only."""
    if not 0.0 < alpha <= 1.0:
        raise ValueError(f"fl.shift_alpha must be in (0, 1], got {alpha!r}")
    has_ef = inner.client_init is not None
    inner_init = inner.client_init

    def client_init(params):
        d = dict(inner_init(params)) if inner_init is not None else {}
        d["h"] = tree_zeros_like(params)
        return d

    def apply(roundtrip, delta, st, key):
        h = jax.tree.map(lambda t: t.astype(jnp.float32), st["h"])
        src = jax.tree.map(lambda d: d.astype(jnp.float32), delta)
        if has_ef:
            src = jax.tree.map(lambda s, e: s + e.astype(jnp.float32),
                               src, st["e"])
        c = roundtrip(jax.tree.map(lambda s, h0: s - h0, src, h), key)
        dhat = jax.tree.map(lambda h0, cl: h0 + cl, h, c)
        st2 = {"h": jax.tree.map(
            lambda b, h0, cl: (h0 + alpha * cl).astype(b.dtype),
            st["h"], h, c)}
        if has_ef:
            st2["e"] = jax.tree.map(
                lambda b, s, dh: (s - dh).astype(b.dtype), st["e"], src, dhat)
        return jax.tree.map(lambda dh, d: dh.astype(d.dtype), dhat, delta), st2

    return inner._replace(
        name=name or f"diana_{inner.name}",
        client_init=client_init, apply=apply, direction="uplink")


def tree_roundtrip(codec: Codec) -> Callable:
    """The tree-level ``decode(encode(.))`` walk both directions share:
    per-leaf subkeys keep the leaves of one payload on independent streams."""

    def roundtrip(src, key):
        leaves, treedef = jax.tree.flatten(src)
        out = []
        for i, v in enumerate(leaves):
            ki = key_combine(key, jnp.uint32(i), jnp)
            out.append(codec.decode(codec.encode(v, ki), ki, v))
        return jax.tree.unflatten(treedef, out)

    return roundtrip


def uplink_apply(codec: Codec) -> Callable:
    """Compile a codec into the per-client uplink hook

        one(delta, state, key) -> (delta_hat, state')

    vmapped over the cohort (or called per client inside the sequential
    scan) by the round driver.  ``state`` is ``{}`` for stateless codecs.
    """
    roundtrip = tree_roundtrip(codec)

    def one(delta, st, key):
        if codec.apply is not None:
            # tree-level composition (DIANA shifts) owns the whole hook
            return codec.apply(roundtrip, delta, st, key)
        if codec.client_init is None:
            return roundtrip(delta, key), st
        # error feedback: compress Delta + e (fp32), bank the new residual
        src = jax.tree.map(
            lambda d, e: d.astype(jnp.float32) + e.astype(jnp.float32),
            delta, st["e"])
        dhat = roundtrip(src, key)
        if codec.finalize is not None:
            ef2 = codec.finalize(src, dhat, st)
        else:
            ef2 = {"e": jax.tree.map(lambda s, h: s - h, src, dhat)}
        return jax.tree.map(lambda h, d: h.astype(d.dtype), dhat, delta), ef2

    return one


def downlink_apply(codec: Codec) -> Callable:
    """Compile a codec into the per-client downlink broadcast hook

        one(params, ref, key) -> params_hat

    The server encodes the model's delta against the client-held reference
    (gathered from the ``"downlink"`` bank); the client reconstructs
    ``params_hat = ref + decode(encode(params - ref))``, which is both its
    round-start point and — committed back to the bank by the round driver —
    its next reference.  Stateless beyond the reference itself, so it is
    exactly replayable from (seed, client, round).

    ``identity`` bypasses the delta arithmetic entirely (``ref + (x - ref)``
    would NOT be bitwise ``x`` in float): the exact pass-through holds here
    like everywhere else, whatever the reference.
    """
    if codec.name == "identity":
        return lambda params, ref, key: params
    roundtrip = tree_roundtrip(codec)

    def one(params, ref, key):
        delta = jax.tree.map(
            lambda p, r: p.astype(jnp.float32) - r.astype(jnp.float32),
            params, ref)
        dhat = roundtrip(delta, key)
        return jax.tree.map(
            lambda r, d, p: (r.astype(jnp.float32) + d).astype(p.dtype),
            ref, dhat, params)

    return one


# ---------------------------------------------------------------------------
# Wire accounting (direction-neutral — both endpoints ship payload trees)
# ---------------------------------------------------------------------------


def wire_bits_total(codec: Codec, tree) -> float:
    """Bits one endpoint pays to ship a whole ``tree``-shaped payload."""
    return float(sum(codec.wire_bits(leaf) for leaf in jax.tree.leaves(tree)))


def dense_bits(params) -> float:
    """The uncompressed cost of shipping a params-shaped tree either way."""
    return float(sum(leaf.size * leaf.dtype.itemsize * 8
                     for leaf in jax.tree.leaves(params)))


def mbytes_per_slot(codec: Codec, params, valid) -> jnp.ndarray:
    """Per-slot megabytes on the wire this round ([C] fp32).

    Today every arriving client pays the codec's static params-shaped cost
    (invalid padding slots pay 0), so this is ``valid * const`` — but it is
    the slot-order array the telemetry histograms bin, and the one place a
    future variable-rate codec changes to make per-client cost honest."""
    bits = wire_bits_total(codec, params)
    return jnp.asarray(valid, jnp.float32) * jnp.float32(bits / 8e6)


def wire_metrics(up: Codec | None, down: Codec | None, params, valid) -> dict:
    """The round's bytes-on-wire keys for its compressed directions (``None``
    for a dense one): each direction's megabytes and compression ratio, and
    ``total_comm_mbytes`` for both, a dense direction charged its dense cost.
    Static per client: every payload is params-shaped."""
    dense = dense_bits(params)
    out, bits = {}, {}
    for direction, codec in (("uplink", up), ("downlink", down)):
        if codec is not None:
            bits[direction] = wire_bits_total(codec, params)
            out[f"{direction}_mbytes"] = valid.sum() * jnp.float32(
                bits[direction] / 8e6)
            out[f"{direction}_compression"] = jnp.float32(dense / bits[direction])
    out["total_comm_mbytes"] = valid.sum() * jnp.float32(
        (bits.get("uplink", dense) + bits.get("downlink", dense)) / 8e6)
    return out


_DEPRECATION_WARNED: set[str] = set()


def _warn_once(old: str, new: str) -> None:
    if old not in _DEPRECATION_WARNED:
        _DEPRECATION_WARNED.add(old)
        warnings.warn(
            f"repro.fed.comm.{old} is deprecated (direction-ambiguous since "
            f"the plane went bidirectional); use {new}",
            DeprecationWarning, stacklevel=3)


def uplink_wire_bits(codec: Codec, params) -> float:
    """Deprecated alias of :func:`wire_bits_total` (one-shot warning)."""
    _warn_once("uplink_wire_bits", "wire_bits_total")
    return wire_bits_total(codec, params)


def uplink_mbytes_per_slot(codec: Codec, params, valid) -> jnp.ndarray:
    """Deprecated alias of :func:`mbytes_per_slot` (one-shot warning)."""
    _warn_once("uplink_mbytes_per_slot", "mbytes_per_slot")
    return mbytes_per_slot(codec, params, valid)


# ---------------------------------------------------------------------------
# Shared per-direction knob validation
# ---------------------------------------------------------------------------


def validate_codec_knobs(fl: FLConfig, direction: str, *needs: str) -> dict:
    """Bind-time bounds checks for one direction's codec knob family.

    THE shared validator: the qsgd/topk/randk factories call it for whichever
    direction they are being built for, so ``fl.uplink_*`` and
    ``fl.downlink_*`` knobs go through identical checks and the two error
    paths cannot drift.  ``needs`` names the knobs a codec actually reads
    (``"bits"``, ``"chunk"``, ``"frac"``, ``"backend"``); returns the
    validated values keyed by those short names.
    """
    if direction not in DIRECTIONS:
        raise ValueError(
            f"unknown codec direction {direction!r}; have {DIRECTIONS}")
    out: dict = {}
    for knob in needs:
        if knob == "backend":
            # the quantize pack path is shared by both directions on purpose:
            # the wire format must match whichever end decodes it
            backend = fl.uplink_backend
            if backend not in ("ref", "pallas"):
                raise ValueError(
                    f"unknown uplink_backend {backend!r}; have ('ref', 'pallas')")
            out[knob] = backend
            continue
        val = getattr(fl, f"{direction}_{knob}")
        if knob == "bits" and val not in BITS_CHOICES:
            raise ValueError(
                f"fl.{direction}_bits must be one of {BITS_CHOICES}, got {val!r}")
        if knob == "chunk" and val < 1:
            raise ValueError(
                f"fl.{direction}_chunk must be >= 1, got {val!r}")
        if knob == "frac" and not 0.0 < val <= 1.0:
            raise ValueError(
                f"fl.{direction}_frac must be in (0, 1], got {val!r}")
        out[knob] = val
    return out


# ---------------------------------------------------------------------------
# Built-in codec factories: make(fl, direction) -> Codec
# ---------------------------------------------------------------------------


def make_identity(fl: FLConfig, direction: str = "uplink") -> Codec:
    """Exact pass-through — the frozen bitwise contract: with
    ``uplink='identity'`` / ``downlink='identity'`` that direction's float op
    sequence is byte-for-byte the no-comm path's (the payload wraps the same
    arrays, no casts, no math)."""
    return Codec(
        name="identity",
        encode=lambda v, key: {"v": v},
        decode=lambda p, key, like: p["v"],
        wire_bits=lambda like: like.size * like.dtype.itemsize * 8,
    )


def _frac_k(frac: float, n: int) -> int:
    return max(1, min(n, int(round(frac * n))))


def make_qsgd(fl: FLConfig, direction: str = "uplink") -> Codec:
    """QSGD-style stochastic quantization to ``{direction}_bits`` signed
    levels with one fp32 scale per ``{direction}_chunk`` values; the
    bit-packed stream comes from ``kernels.quantize`` (``uplink_backend``
    selects the in-jit jnp oracle or the Pallas kernel for BOTH directions —
    bitwise-identical)."""
    k = validate_codec_knobs(fl, direction, "bits", "chunk", "backend")
    bits, chunk, backend = k["bits"], k["chunk"], k["backend"]
    pb = packed_width(chunk, bits)           # validates chunk % (8//bits)

    def _nc(n: int) -> int:
        return -(-n // chunk)

    def encode(v, key):
        flat = v.astype(jnp.float32).reshape(-1)
        nc = _nc(flat.size)
        flat = jnp.pad(flat, (0, nc * chunk - flat.size))
        keys = key_combine(key, jnp.arange(nc, dtype=jnp.uint32), jnp)
        packed, scale = quantize_pack(flat.reshape(nc, chunk), keys,
                                      bits=bits, backend=backend)
        return {"q": packed, "s": scale}

    def decode(p, key, like):
        v2 = unpack_dequantize(p["q"], p["s"], chunk=chunk, bits=bits,
                               backend=backend)
        return (v2.reshape(-1)[: like.size].reshape(like.shape)
                .astype(like.dtype))

    def wire_bits(like):
        nc = _nc(like.size)
        return nc * pb * 8 + nc * 32         # packed levels + fp32 scales

    return Codec("qsgd", encode, decode, wire_bits, seeded=True)


def make_topk_raw(fl: FLConfig, direction: str = "uplink") -> Codec:
    """Magnitude top-k per leaf: the k largest-|.| values plus their int32
    positions.  Biased — register through :func:`with_error_feedback` (the
    built-in ``topk`` entry) unless you know why you want it raw."""
    frac = validate_codec_knobs(fl, direction, "frac")["frac"]

    def encode(v, key):
        flat = v.astype(jnp.float32).reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(flat), _frac_k(frac, flat.size))
        idx = idx.astype(jnp.int32)
        return {"v": jnp.take(flat, idx), "i": idx}

    def decode(p, key, like):
        flat = jnp.zeros((like.size,), jnp.float32).at[p["i"]].set(p["v"])
        return flat.reshape(like.shape).astype(like.dtype)

    def wire_bits(like):
        return _frac_k(frac, like.size) * (32 + 32)   # fp32 value + int32 pos

    return Codec("topk_raw", encode, decode, wire_bits)


def make_randk(fl: FLConfig, direction: str = "uplink") -> Codec:
    """Random-k sparsification with the unbiased ``n/k`` scaling.  The k
    coordinates are the first k outputs of the swap-or-not permutation of
    ``[0, n)`` under the round key (``kernels.rr_perm``) — a uniformly
    random k-subset the DECODER regenerates from the same key, so only the
    k values travel (no index bytes)."""
    frac = validate_codec_knobs(fl, direction, "frac")["frac"]
    rounds = fl.rr_rounds

    def _idx(key, n: int):
        k = _frac_k(frac, n)
        return swap_or_not(jnp.arange(k, dtype=jnp.uint32), jnp.uint32(n),
                           key, rounds, jnp).astype(jnp.int32)

    def encode(v, key):
        flat = v.astype(jnp.float32).reshape(-1)
        return {"v": jnp.take(flat, _idx(key, flat.size))}

    def decode(p, key, like):
        n = like.size
        scale = jnp.float32(n / _frac_k(frac, n))
        flat = jnp.zeros((n,), jnp.float32).at[_idx(key, n)].set(p["v"] * scale)
        return flat.reshape(like.shape).astype(like.dtype)

    def wire_bits(like):
        return _frac_k(frac, like.size) * 32          # values only

    return Codec("randk", encode, decode, wire_bits, seeded=True)


# ---------------------------------------------------------------------------
# Registry: name -> CodecEntry(make, declared direction)
# ---------------------------------------------------------------------------


class CodecEntry(NamedTuple):
    """One :data:`CODECS` record: the factory plus its declared direction.

    Calling the entry builds the codec — ``entry(fl)`` keeps the historical
    single-argument call working (uplink knobs); direction-aware factories
    (any accepting a ``direction`` parameter) receive the direction they are
    being built for, which routes the matching knob family."""

    make: Callable
    direction: str = "both"

    def __call__(self, fl: FLConfig, direction: str = "uplink") -> Codec:
        make = self.make
        if isinstance(make, CodecEntry):      # an entry re-registered as-is
            return make(fl, direction)
        try:
            wants = "direction" in inspect.signature(make).parameters
        except (TypeError, ValueError):
            wants = False
        return make(fl, direction) if wants else make(fl)


CODECS: dict[str, CodecEntry] = {
    "identity": CodecEntry(make_identity, "both"),
    "qsgd": CodecEntry(make_qsgd, "both"),
    # top-k without error feedback is simply a worse algorithm (the bias
    # never washes out) — the registered entry is the EF-SGD composition,
    # which pins it to the uplink (the residual lives on the client)
    "topk": CodecEntry(
        lambda fl, direction="uplink": with_error_feedback(
            make_topk_raw(fl, direction), name="topk"),
        "uplink"),
    "randk": CodecEntry(make_randk, "both"),
    "ef_qsgd": CodecEntry(
        lambda fl, direction="uplink": with_error_feedback(
            make_qsgd(fl, direction)),
        "uplink"),
    "ef_randk": CodecEntry(
        lambda fl, direction="uplink": with_error_feedback(
            make_randk(fl, direction)),
        "uplink"),
    # DIANA-RR learned shifts: the compressor sees Delta - h, both ends move
    # h by shift_alpha * C(Delta - h) — uplink-only (the shift bank is
    # client state, exactly like EF residuals)
    "diana_qsgd": CodecEntry(
        lambda fl, direction="uplink": with_diana_shift(
            make_qsgd(fl, direction), fl.shift_alpha),
        "uplink"),
    "diana_randk": CodecEntry(
        lambda fl, direction="uplink": with_diana_shift(
            make_randk(fl, direction), fl.shift_alpha),
        "uplink"),
    "diana_topk": CodecEntry(
        lambda fl, direction="uplink": with_diana_shift(
            with_error_feedback(make_topk_raw(fl, direction)),
            fl.shift_alpha, name="diana_topk"),
        "uplink"),
}


def register_codec(name: str, make: Callable, *, direction: str = "both",
                   overwrite: bool = False) -> None:
    """Register ``make(fl[, direction]) -> Codec`` under ``name``.

    ``direction`` declares the capability (``"uplink"`` / ``"downlink"`` /
    ``"both"``) that :func:`build_codec` routes ``fl.uplink`` /
    ``fl.downlink`` against.  A codec whose composition keeps per-client
    compressor state (error feedback, DIANA shifts) is uplink-only, and the
    conflict is rejected HERE, at registration time, with the knobs named —
    historically it only surfaced as a shape error inside jit."""
    if direction not in ("uplink", "downlink", "both"):
        raise ValueError(
            f"codec direction must be 'uplink', 'downlink' or 'both', "
            f"got {direction!r}")
    if not overwrite and name in CODECS:
        raise ValueError(
            f"codec {name!r} already registered (pass overwrite=True to replace)")
    entry = CodecEntry(make, direction)
    if direction != "uplink":
        try:
            probe = entry(FLConfig())
        except Exception:
            # the factory needs non-default knobs to build; build_codec runs
            # the identical check at bind time instead
            probe = None
        if probe is not None and (probe.client_init is not None
                                  or probe.direction == "uplink"):
            raise ValueError(
                f"codec {name!r} declares direction={direction!r} but its "
                f"composition keeps per-client compressor state (client_init "
                f"is set: an error-feedback residual or DIANA shift).  That "
                f"state lives on the CLIENT and the downlink encoder is the "
                f"SERVER, so fl.downlink={name!r} could never honor it — "
                f"register it with direction='uplink' (routing it through "
                f"fl.uplink only), or drop the with_error_feedback / "
                f"with_diana_shift wrapper from this entry.")
    CODECS[name] = entry


def build_codec(fl: FLConfig, direction: str = "uplink") -> Codec:
    """Resolve one direction's configured codec to a bound Codec (bind-time
    validation: unknown names, direction-incapable codecs and bad knob
    values raise here, not at the first round)."""
    if direction not in DIRECTIONS:
        raise ValueError(
            f"unknown codec direction {direction!r}; have {DIRECTIONS}")
    name = getattr(fl, direction)
    if name not in CODECS:
        raise ValueError(
            f"unknown {direction} codec {name!r}; have {sorted(CODECS)}")
    entry = CODECS[name]
    declared = getattr(entry, "direction", "both")
    if declared not in ("both", direction):
        capable = sorted(n for n, e in CODECS.items()
                         if getattr(e, "direction", "both") in ("both", direction))
        raise ValueError(
            f"fl.{direction}={name!r}, but codec {name!r} is registered "
            f"{declared}-only; {direction}-capable codecs: {capable}")
    codec = entry(fl, direction) if isinstance(entry, CodecEntry) else entry(fl)
    if direction == "downlink" and (codec.client_init is not None
                                    or codec.direction == "uplink"):
        # bind-time twin of the register_codec rejection, for factories whose
        # registration probe could not build under default knobs
        raise ValueError(
            f"fl.downlink={name!r} resolves to a codec keeping per-client "
            f"compressor state (error feedback / DIANA shift) — client-side "
            f"state cannot ride the server's broadcast; use a stateless "
            f"downlink codec (e.g. 'identity', 'qsgd', 'randk').")
    return codec
