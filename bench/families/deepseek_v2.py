"""The DeepSeek-V2 family (arXiv:2405.04434): multi-head latent attention
(MLA) with YaRN rotary scaling in every layer; ``first_k_dense_replace``
leading layers with a dense SwiGLU under ``first``, the rest DeepSeekMoE
layers under ``blocks``: a softmax router over all routed experts with greedy
top-k and unnormalised gates, the routed experts this chip holds, shared
experts, and the sequence-wise balance loss.  Untied head.

The plain reference of the configuration as run, in float32: each held
expert is computed on every token and weighted by its gate where the token
chose it (zero elsewhere); what the experts held on other chips would add is
left out, as in the program.  The rotary part rotates the pairs (i, i + 32)
of each 64-wide rope vector, as the program does (DeepSeek's code permutes
interleaved pairs first: a fixed relabelling of the rope columns of ``wq``
and ``wkr``, the same function class and the same work).

``SCOPES`` are the names the program gives these layers inside ``blocks``;
loading this module adds them to the names ``bench/scopes.py`` reduces, so
that a traced run's ``RunRecord.scopes`` carries them for the readers
``moe_ms``, ``experts_ms``, ``moe_dispatch_ms``, ``mla_ms`` and
``experts_roofline``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import scopes
from bench.reference import F32, HIGHEST, mm, rmsnorm

SCOPES = ("mla", "moe", "router", "moe_dispatch", "experts", "moe_combine",
          "shared_experts")
scopes.SCOPES = scopes.SCOPES | frozenset(SCOPES)

# What the reference implements of the published config; anything else is refused.
_FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "norm_topk_prob": False,
          "routed_scaling_factor": 1, "q_lora_rank": None, "moe_layer_freq": 1,
          "n_group": 1, "topk_group": 1, "seq_aux": True, "attention_bias": False,
          "hidden_act": "silu", "tie_word_embeddings": False}


@dataclass(frozen=True)
class Shape:
    """A DeepSeek-V2 model's sizes as run: ``experts`` routed experts scored
    by the router, of which ``held`` (from ``first_held``) are on this chip."""

    layers: int           # every layer, the leading dense ones included
    first_dense: int
    d_model: int
    heads: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    kv_lora: int
    d_ff: int             # the dense layers' SwiGLU width
    expert_ff: int
    experts: int
    held: int
    first_held: int
    top_k: int
    shared: int
    vocab: int
    rope_theta: float
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_eps: float
    dtype: str
    init_std: float
    aux_alpha: float
    tied: bool = False

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        for key, want in _FIXED.items():
            if cfg.get(key) != want:
                raise NotImplementedError(f"the deepseek_v2 family runs {key}={want!r}, "
                                          f"not {cfg.get(key)!r}")
        rs = cfg["rope_scaling"]
        if rs["type"] != "yarn":
            raise NotImplementedError(f"rope_scaling type {rs['type']!r}")
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            first_dense=int(cfg["first_k_dense_replace"]),
            d_model=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            qk_nope=int(cfg["qk_nope_head_dim"]),
            qk_rope=int(cfg["qk_rope_head_dim"]),
            v_dim=int(cfg["v_head_dim"]),
            kv_lora=int(cfg["kv_lora_rank"]),
            d_ff=int(cfg["intermediate_size"]),
            expert_ff=int(cfg["moe_intermediate_size"]),
            experts=int(cfg["published"]["n_routed_experts"]),
            held=int(cfg["n_routed_experts"]),
            first_held=int(cfg["first_held_expert"]),
            top_k=int(cfg["num_experts_per_tok"]),
            shared=int(cfg["n_shared_experts"]),
            vocab=int(cfg["vocab_size"]),
            rope_theta=float(cfg["rope_theta"]),
            yarn_factor=float(rs["factor"]),
            yarn_original_max=int(rs["original_max_position_embeddings"]),
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            yarn_mscale=float(rs["mscale"]),
            yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            dtype=str(cfg["torch_dtype"]),
            init_std=float(cfg["assumed"]["init_std"]),
            aux_alpha=float(cfg["assumed"]["aux_loss_alpha"]),
        )


def _mla_leaves(path: str, n: int, s: Shape) -> dict:
    D, H = s.d_model, s.heads
    return {
        f"{path}/ln1/scale": ((n, D), "ones"),
        f"{path}/attn/wq": ((n, D, H * (s.qk_nope + s.qk_rope)), "normal"),
        f"{path}/attn/wdkv": ((n, D, s.kv_lora), "normal"),
        f"{path}/attn/wkr": ((n, D, s.qk_rope), "normal"),
        f"{path}/attn/kv_norm/scale": ((n, s.kv_lora), "ones"),
        f"{path}/attn/wuk": ((n, s.kv_lora, H * s.qk_nope), "normal"),
        f"{path}/attn/wuv": ((n, s.kv_lora, H * s.v_dim), "normal"),
        f"{path}/attn/wo": ((n, H * s.v_dim, D), "normal"),
        f"{path}/ln2/scale": ((n, D), "ones"),
    }


def _swiglu_leaves(path: str, lead: tuple, d: int, f: int) -> dict:
    return {f"{path}/gate": (lead + (d, f), "normal"), f"{path}/up": (lead + (d, f), "normal"),
            f"{path}/down": (lead + (f, d), "normal")}


def leaf_shapes(s: Shape) -> dict:
    """``{path: (shape, kind)}`` in the program's layout (``models/model.py``:
    ``first`` and ``blocks`` stacked on a leading layer axis)."""
    F, L, D = s.first_dense, s.layers - s.first_dense, s.d_model
    return {
        "embed": ((s.vocab, D), "normal"),
        **_mla_leaves("first", F, s),
        **_swiglu_leaves("first/mlp", (F,), D, s.d_ff),
        **_mla_leaves("blocks", L, s),
        "blocks/moe/router": ((L, D, s.experts), "normal"),
        **_swiglu_leaves("blocks/moe/experts", (L, s.held), D, s.expert_ff),
        **_swiglu_leaves("blocks/moe/shared", (L,), D, s.expert_ff * s.shared),
        "final_norm/scale": ((D,), "ones"),
        "lm_head": ((D, s.vocab), "normal"),
    }


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(s: Shape) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies [qk_rope / 2], in float64."""
    dim, base = s.qk_rope, s.rope_theta

    def corr(rot):
        return dim * math.log(s.yarn_original_max / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(s.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(s.yarn_beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / s.yarn_factor * ramp + extra * (1.0 - ramp)


def softmax_scale(s: Shape) -> float:
    m = yarn_mscale(s.yarn_factor, s.yarn_mscale_all_dim)
    return m * m / math.sqrt(s.qk_nope + s.qk_rope)


def rope(s: Shape, x):
    """x [B, T, H, qk_rope]: rotate (first half, second half) at the YaRN
    frequencies, cos and sin scaled by mscale / mscale_all_dim."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(s)[None, :]
    r = yarn_mscale(s.yarn_factor, s.yarn_mscale) / yarn_mscale(s.yarn_factor,
                                                                s.yarn_mscale_all_dim)
    cos = jnp.asarray(np.cos(ang) * r, F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * r, F32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def mla(s: Shape, quant, p: dict, h):
    """h + MLA(rmsnorm(h)), causal, every head at qk_nope + qk_rope."""
    Bsz, T, _ = h.shape
    H = s.heads
    a = p["attn"]
    x = rmsnorm(p["ln1"]["scale"], h, s.norm_eps)
    q = mm(quant, x, a["wq"]).reshape(Bsz, T, H, s.qk_nope + s.qk_rope)
    c = rmsnorm(a["kv_norm"]["scale"], mm(quant, x, a["wdkv"]), s.norm_eps)
    k_pe = rope(s, mm(quant, x, a["wkr"])[:, :, None, :])
    q = jnp.concatenate([q[..., :s.qk_nope], rope(s, q[..., s.qk_nope:])], axis=-1)
    k = jnp.concatenate([mm(quant, c, a["wuk"]).reshape(Bsz, T, H, s.qk_nope),
                         jnp.broadcast_to(k_pe, (Bsz, T, H, s.qk_rope))], axis=-1)
    v = mm(quant, c, a["wuv"]).reshape(Bsz, T, H, s.v_dim)
    scores = jnp.einsum("bthd,bshd->bhts", quant(q), quant(k),
                        precision=HIGHEST) * softmax_scale(s)
    causal = np.tril(np.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", quant(probs), quant(v), precision=HIGHEST)
    return h + mm(quant, o.reshape(Bsz, T, H * s.v_dim), a["wo"])


def swiglu(quant, m: dict, x):
    return mm(quant, jax.nn.silu(mm(quant, x, m["gate"])) * mm(quant, x, m["up"]), m["down"])


def dense_layer(s: Shape, quant, p: dict, h):
    """A leading layer: MLA, then a SwiGLU of width d_ff; no auxiliary loss."""
    h = mla(s, quant, p, h)
    return h + swiglu(quant, p["mlp"], rmsnorm(p["ln2"]["scale"], h, s.norm_eps)), \
        jnp.float32(0.0)


def moe_layer(s: Shape, quant, p: dict, h):
    """MLA, then the held routed experts, each weighted by its gate where the
    token chose it, plus the shared experts; returns the sequence-wise
    balance loss over all experts."""
    h = mla(s, quant, p, h)
    Bsz, T, D = h.shape
    m = p["moe"]
    x = rmsnorm(p["ln2"]["scale"], h, s.norm_eps)
    probs = jax.nn.softmax(mm(quant, x, m["router"]), axis=-1)       # [B, T, E]
    _, idx = jax.lax.top_k(probs, s.top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, s.experts, dtype=F32), axis=2)   # [B, T, E]
    gates = probs * chosen
    y = jnp.zeros_like(h)
    for j in range(s.held):
        e = {k: v[j] for k, v in m["experts"].items()}
        y = y + gates[..., s.first_held + j, None] * swiglu(quant, e, x)
    y = y + swiglu(quant, m["shared"], x)
    f = jnp.sum(chosen, axis=1) * (s.experts / (s.top_k * T))        # [B, E]
    aux = s.aux_alpha * jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    return h + y, aux


def layers(s: Shape) -> list:
    return ([("first", l, dense_layer) for l in range(s.first_dense)]
            + [("blocks", l, moe_layer) for l in range(s.layers - s.first_dense)])


def param_count(s: Shape) -> int:
    return sum(int(np.prod(shape)) for shape, _ in leaf_shapes(s).values())


def moe_rows_per_step(s: Shape, batch: int, seq_len: int) -> float:
    """(token, expert) rows a MoE layer's held experts compute in one step,
    in expectation under uniform routing: B T k held / E."""
    return batch * seq_len * s.top_k * s.held / s.experts


def train_flops_per_token(s: Shape, seq_len: int) -> float:
    """6 N' + 6 L H (qk + v) T: N' counts the parameters a token passes
    through on this chip (the untied input table is a lookup; of the routed
    experts, top_k * held / experts of one expert per MoE layer, which is
    what a token is routed to here in expectation), and attention's score
    and value products count at their own widths, over every layer."""
    moe_layers = s.layers - s.first_dense
    expert = 3 * s.d_model * s.expert_ff
    n = (param_count(s) - s.vocab * s.d_model
         - moe_layers * s.held * expert
         + moe_layers * expert * s.top_k * s.held / s.experts)
    return 6.0 * n + 6.0 * s.layers * s.heads * (s.qk_nope + s.qk_rope + s.v_dim) * seq_len


def arch_config(s: Shape, name: str):
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig

    return ArchConfig(
        name=name, family="moe", n_layers=s.layers, first_dense=s.first_dense,
        d_model=s.d_model, n_heads=s.heads, n_kv_heads=s.heads, head_dim=s.v_dim,
        d_ff=s.d_ff, vocab=s.vocab, rope_theta=s.rope_theta, tie_embeddings=False,
        norm_eps=s.norm_eps, dtype=s.dtype,
        mla=MLAConfig(q_lora=0, kv_lora=s.kv_lora, qk_nope_dim=s.qk_nope,
                      qk_rope_dim=s.qk_rope, v_head_dim=s.v_dim,
                      yarn=YaRNConfig(factor=s.yarn_factor, original_max=s.yarn_original_max,
                                      beta_fast=s.yarn_beta_fast, beta_slow=s.yarn_beta_slow,
                                      mscale=s.yarn_mscale,
                                      mscale_all_dim=s.yarn_mscale_all_dim)),
        moe=MoEConfig(num_experts=s.experts, top_k=s.top_k, num_shared=s.shared,
                      expert_ff=s.expert_ff, held=s.held, first_held=s.first_held,
                      aux_alpha=s.aux_alpha))
