"""Model assembly: init / loss / prefill / decode for every arch family.

One ``Model`` object per ArchConfig with a uniform API used by the FL stack,
the serving path and the dry-run:

  * ``init(key) -> params``
  * ``loss(params, batch) -> (scalar, metrics)``        (train_step objective)
  * ``init_cache(batch_size, cache_len) -> cache``      (decode state, zeros)
  * ``prefill(params, batch, cache_len) -> (logits, cache)``
  * ``decode_step(params, token, cache, ring=False) -> (logits, cache)``

Layers are stacked on a leading axis and scanned (compact HLO for 80-layer
configs); ``cfg.remat == "full"`` wraps the per-layer body in jax.checkpoint.
A moe stack's ``cfg.first_dense`` leading layers (MLA + dense FFN) are
stacked under ``first`` and run before the ``blocks`` scan.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from . import blocks as B
from .layers import embed_init, dense_init, rmsnorm, rmsnorm_init, softmax_xent
from .mamba2 import dims as ssm_dims


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def sinusoid(positions: jnp.ndarray, dim: int) -> jnp.ndarray:
    """Absolute sinusoidal embeddings (used when rope_kind == 'none')."""
    half = dim // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(1, half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[..., :dim]


_FWD = {
    "dense": B.dense_block_forward,
    "vlm": B.dense_block_forward,
    "moe": B.moe_block_forward,
    "ssm": B.ssm_block_forward,
    "hybrid": B.hybrid_block_forward,
}
_DEC = {
    "dense": B.dense_block_decode,
    "vlm": B.dense_block_decode,
    "moe": B.moe_block_decode,
    "ssm": B.ssm_block_decode,
    "hybrid": B.hybrid_block_decode,
}
_INIT = {
    "dense": B.dense_block_init,
    "vlm": B.dense_block_init,
    "moe": B.moe_block_init,
    "ssm": B.ssm_block_init,
    "hybrid": B.hybrid_block_init,
}


def _zero_stats(cfg: ArchConfig):
    """What the layer scan sums besides the hidden state: the auxiliary
    loss, and for a moe stack also the rows its experts computed."""
    if cfg.family == "moe":
        return {"aux": jnp.float32(0.0), "moe_rows": jnp.int32(0)}
    return jnp.float32(0.0)


def _aux(stats):
    return stats["aux"] if isinstance(stats, dict) else stats


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    def __post_init__(self):
        if self.cfg.first_dense and self.cfg.family != "moe":
            raise ValueError(f"first_dense is a moe stack's leading layers; "
                             f"{self.cfg.name} is {self.cfg.family!r}")

    # ------------------------------------------------------------------ init

    def init(self, key) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 8)
        p: dict = {"embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dt)}
        fam = "dense" if cfg.family == "audio" else cfg.family
        init_one = _INIT.get(fam, B.dense_block_init)
        if cfg.family == "audio":
            p["enc_blocks"] = jax.vmap(lambda k: B.enc_block_init(k, cfg, dt))(
                jax.random.split(keys[1], cfg.enc_layers)
            )
            p["enc_norm"] = rmsnorm_init(cfg.d_model, dt)
            p["blocks"] = jax.vmap(lambda k: B.dec_block_init(k, cfg, dt))(
                jax.random.split(keys[2], cfg.n_layers)
            )
        else:
            p["blocks"] = jax.vmap(lambda k: init_one(k, cfg, dt))(
                jax.random.split(keys[2], cfg.n_layers - cfg.first_dense)
            )
        if cfg.first_dense:
            p["first"] = jax.vmap(lambda k: B.first_block_init(k, cfg, dt))(
                jax.random.split(keys[7], cfg.first_dense)
            )
        p["final_norm"] = rmsnorm_init(cfg.d_model, dt)
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(keys[3], cfg.d_model, cfg.vocab, dt)
        if cfg.family == "vlm":
            p["patch_proj"] = dense_init(keys[4], cfg.d_model, cfg.d_model, dt)
        if cfg.mtp:
            p["mtp_block"] = _INIT[cfg.family](keys[5], cfg, dt)
            p["mtp_proj"] = dense_init(keys[6], 2 * cfg.d_model, cfg.d_model, dt)
        return p

    # ------------------------------------------------------------- backbone

    def _first(self, params, h, positions, window):
        """The leading dense layers -> (h, their caches stacked)."""
        cfg = self.cfg

        def body(h, layer_params):
            return B.first_block_forward(layer_params, cfg, h, positions, window=window)

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        return jax.lax.scan(body, h, params["first"])

    def _backbone(self, params, h, positions, *, collect_cache=False, window=0):
        cfg = self.cfg
        fwd = _FWD[cfg.family if cfg.family != "audio" else "dense"]
        if cfg.first_dense:
            h, first_caches = self._first(params, h, positions, window)

        def body(carry, layer_params):
            h, aux = carry
            if cfg.family in ("ssm", "hybrid"):
                h, a, cache = fwd(layer_params, cfg, h, positions, keep_cache=collect_cache)
            else:
                h, a, cache = fwd(layer_params, cfg, h, positions, window=window,
                                  keep_cache=collect_cache)
            if cfg.opt_seq_shard:
                # perf iteration: sequence-shard the residual stream over the
                # model axis between blocks (Korthikanti-style sequence
                # parallelism) — turns per-layer activation all-reduces into
                # reduce-scatter + all-gather pairs at half the volume
                from jax.sharding import PartitionSpec as _P

                h = jax.lax.with_sharding_constraint(h, _P(None, "model", None))
            return (h, jax.tree.map(jnp.add, aux, a)), cache

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        (h, aux), caches = jax.lax.scan(body, (h, _zero_stats(cfg)), params["blocks"],
                                        unroll=cfg.scan_unroll)
        if cfg.first_dense and collect_cache:
            caches = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), first_caches, caches)
        return h, aux, caches

    def _decode_backbone(self, params, h, pos, cache_layers, *, ring=False, window=0):
        cfg = self.cfg
        F = cfg.first_dense
        if F:
            def first_body(h, xs):
                return B.first_block_decode(xs[0], cfg, h, pos, xs[1], ring=ring)

            h, first_new = jax.lax.scan(
                first_body, h, (params["first"], jax.tree.map(lambda c: c[:F], cache_layers)))
            cache_layers = jax.tree.map(lambda c: c[F:], cache_layers)

        def body(h, xs):
            layer_params, layer_cache = xs
            if cfg.family == "audio":
                h, nc = B.dec_block_decode(layer_params, cfg, h, pos, layer_cache, ring=ring)
            elif cfg.family in ("ssm",):
                h, nc = B.ssm_block_decode(layer_params, cfg, h, pos, layer_cache)
            elif cfg.family == "hybrid":
                h, nc = B.hybrid_block_decode(layer_params, cfg, h, pos, layer_cache)
            elif cfg.family == "moe":
                h, nc = B.moe_block_decode(layer_params, cfg, h, pos, layer_cache, ring=ring)
            else:
                h, nc = B.dense_block_decode(layer_params, cfg, h, pos, layer_cache,
                                             window=window, ring=ring)
            return h, nc

        h, new_layers = jax.lax.scan(body, h, (params["blocks"], cache_layers),
                                     unroll=cfg.scan_unroll)
        if F:
            new_layers = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), first_new, new_layers)
        return h, new_layers

    def _logits(self, params, h):
        if self.cfg.tie_embeddings:
            return h @ params["embed"].T
        return h @ params["lm_head"]

    # ----------------------------------------------------------------- loss

    def loss(self, params, batch) -> tuple[jnp.ndarray, dict]:
        cfg = self.cfg
        toks = batch["tokens"]
        inputs, labels = toks[..., :-1], toks[..., 1:]
        Bsz, S = inputs.shape

        if cfg.family == "audio":
            return self._loss_encdec(params, batch, inputs, labels)

        with jax.named_scope("embed"):
            h = params["embed"][inputs]
        offset = 0
        if cfg.family == "vlm":
            patches = batch["patches"].astype(h.dtype) @ params["patch_proj"]
            h = jnp.concatenate([patches, h], axis=1)
            offset = patches.shape[1]
        positions = jnp.arange(h.shape[1])
        if cfg.rope_kind == "none" and cfg.family not in ("ssm",):
            h = h + sinusoid(positions, cfg.d_model)[None].astype(h.dtype)

        with jax.named_scope("blocks"):
            h, stats, _ = self._backbone(params, h, positions, window=cfg.sliding_window)
        with jax.named_scope("lm_head"):
            h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
            h_text = h[:, offset:]
            logits = self._logits(params, h_text)
            ce = softmax_xent(logits, labels, onehot=cfg.opt_onehot_xent).mean()
        aux = _aux(stats)
        loss = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if isinstance(stats, dict):
            metrics["moe_rows"] = stats["moe_rows"]

        if cfg.mtp and S >= 2:
            # multi-token prediction: combine h_t with emb(x_{t+1}) -> predict x_{t+2}
            with jax.named_scope("lm_head"):
                nxt = params["embed"][inputs[:, 1:]]
                comb = jnp.concatenate([h_text[:, :-1], nxt], axis=-1) @ params["mtp_proj"]
                pos2 = jnp.arange(S - 1)
                fwd = _FWD[cfg.family]
                hm, mtp_stats, _ = fwd(params["mtp_block"], cfg, comb, pos2, keep_cache=False)
                mtp_aux = _aux(mtp_stats)
                mtp_logits = self._logits(params, rmsnorm(params["final_norm"], hm, cfg.norm_eps))
                mtp_ce = softmax_xent(mtp_logits, labels[:, 1:],
                                      onehot=cfg.opt_onehot_xent).mean()
            loss = loss + cfg.mtp_coef * (mtp_ce + mtp_aux)
            metrics["mtp_ce"] = mtp_ce
        return loss, metrics

    def _encode(self, params, frames):
        cfg = self.cfg
        pos = jnp.arange(frames.shape[1])
        h = frames.astype(_dtype(cfg)) + sinusoid(pos, cfg.d_model)[None].astype(_dtype(cfg))

        def body(h, layer_params):
            return B.enc_block_forward(layer_params, cfg, h, pos), None

        h, _ = jax.lax.scan(body, h, params["enc_blocks"])
        return rmsnorm(params["enc_norm"], h, cfg.norm_eps)

    def _loss_encdec(self, params, batch, inputs, labels):
        cfg = self.cfg
        enc_out = self._encode(params, batch["frames"])
        enc_pos = jnp.arange(enc_out.shape[1])
        pos = jnp.arange(inputs.shape[1])
        h = params["embed"][inputs] + sinusoid(pos, cfg.d_model)[None].astype(_dtype(cfg))

        def body(h, layer_params):
            k, v = B.cross_kv(layer_params, cfg, enc_out)
            h, _ = B.dec_block_forward(layer_params, cfg, h, pos, (k, v, enc_pos),
                                       keep_cache=False)
            return h, None

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, params["blocks"])
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        ce = softmax_xent(logits, labels, onehot=cfg.opt_onehot_xent).mean()
        return ce, {"ce": ce, "aux": jnp.float32(0.0)}

    # ---------------------------------------------------------------- serve

    def cache_spec(self, batch_size: int, cache_len: int, src_len: int = 0) -> dict:
        """Zeros-free structural spec: dict of (shape, dtype) for the cache."""
        cfg = self.cfg
        dt = _dtype(cfg)
        L, Bsz, S = cfg.n_layers, batch_size, cache_len
        hd = cfg.hd()
        spec: dict = {}
        if cfg.family in ("dense", "vlm"):
            spec = {"k": ((L, Bsz, S, cfg.n_kv_heads, hd), dt),
                    "v": ((L, Bsz, S, cfg.n_kv_heads, hd), dt)}
        elif cfg.family == "moe":
            m = cfg.mla
            spec = {"c_kv": ((L, Bsz, S, m.kv_lora), dt),
                    "k_rope": ((L, Bsz, S, m.qk_rope_dim), dt)}
        elif cfg.family == "ssm":
            d_inner, H, P, N = ssm_dims(cfg)
            conv_ch = d_inner + 2 * N
            spec = {"state": ((L, Bsz, H, P, N), jnp.float32),
                    "conv": ((L, Bsz, cfg.ssm.conv_width - 1, conv_ch), dt)}
        elif cfg.family == "hybrid":
            d_inner, H, P, N = ssm_dims(cfg)
            conv_ch = d_inner + 2 * N
            W = min(S, cfg.sliding_window or S)
            spec = {"k": ((L, Bsz, W, cfg.n_kv_heads, hd), dt),
                    "v": ((L, Bsz, W, cfg.n_kv_heads, hd), dt),
                    "state": ((L, Bsz, H, P, N), jnp.float32),
                    "conv": ((L, Bsz, cfg.ssm.conv_width - 1, conv_ch), dt)}
        elif cfg.family == "audio":
            spec = {"k": ((L, Bsz, S, cfg.n_kv_heads, hd), dt),
                    "v": ((L, Bsz, S, cfg.n_kv_heads, hd), dt),
                    "xk": ((L, Bsz, src_len or cfg.src_frames, cfg.n_kv_heads, hd), dt),
                    "xv": ((L, Bsz, src_len or cfg.src_frames, cfg.n_kv_heads, hd), dt)}
        return spec

    def init_cache(self, batch_size: int, cache_len: int, src_len: int = 0) -> dict:
        layers = {k: jnp.zeros(shape, d)
                  for k, (shape, d) in self.cache_spec(batch_size, cache_len, src_len).items()}
        return {"layers": layers, "pos": jnp.zeros((), jnp.int32)}

    def prefill(self, params, batch, cache_len: int):
        """Full forward collecting decode-ready caches (tests + serving)."""
        cfg = self.cfg
        toks = batch["tokens"]
        Bsz, T = toks.shape
        h = params["embed"][toks]
        if cfg.family == "vlm":
            patches = batch["patches"].astype(h.dtype) @ params["patch_proj"]
            h = jnp.concatenate([patches, h], axis=1)
        positions = jnp.arange(h.shape[1])
        if cfg.rope_kind == "none" and cfg.family != "ssm":
            h = h + sinusoid(positions, cfg.d_model)[None].astype(h.dtype)

        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
            enc_pos = jnp.arange(enc_out.shape[1])

            def body(h, layer_params):
                k, v = B.cross_kv(layer_params, cfg, enc_out)
                h, cache = B.dec_block_forward(layer_params, cfg, h, positions, (k, v, enc_pos))
                return h, {**cache, "xk": k, "xv": v}

            h, caches = jax.lax.scan(body, h, params["blocks"])
        else:
            h, _, caches = self._backbone(params, h, positions, collect_cache=True,
                                          window=cfg.sliding_window)
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = self._logits(params, h[:, -1:])
        Ttot = h.shape[1]
        seq_keys = {"k", "v", "c_kv", "k_rope"}  # sequence-indexed cache entries
        src_len = batch["frames"].shape[1] if cfg.family == "audio" else 0
        spec = self.cache_spec(toks.shape[0], cache_len, src_len)

        def fit(path, x):
            key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if key in seq_keys:
                return _fit_cache_entry(x, cache_len=spec[key][0][2], t=Ttot)
            return x

        layers = jax.tree_util.tree_map_with_path(fit, caches)
        return logits, {"layers": layers, "pos": jnp.asarray(Ttot, jnp.int32)}

    def decode_step(self, params, token, cache, *, ring=False, window=0):
        """token [B, 1] int32 -> (logits [B,1,V], updated cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        h = params["embed"][token]
        if cfg.rope_kind == "none" and cfg.family != "ssm":
            h = h + sinusoid(jnp.full((1,), pos), cfg.d_model)[None].astype(h.dtype)
        h, new_layers = self._decode_backbone(params, h, pos, cache["layers"],
                                              ring=ring, window=window)
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return self._logits(params, h), {"layers": new_layers, "pos": pos + 1}


def _fit_cache_entry(x, *, cache_len: int, t: int):
    """Fit a prefill-produced per-layer cache entry into the serve layout.

    Sequence-indexed entries ([L,B,T,...] with T == t) are placed at slots
    ``p % cache_len`` (ring-consistent); state-like entries pass through.
    """
    if x.ndim >= 3 and x.shape[2] == t:
        S = cache_len
        out_shape = x.shape[:2] + (S,) + x.shape[3:]
        out = jnp.zeros(out_shape, x.dtype)
        start = max(0, t - S)
        keep = x[:, :, start:t]
        slots = (jnp.arange(start, t)) % S
        return out.at[:, :, slots].set(keep)
    return x


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
