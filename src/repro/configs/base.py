"""Config system: model architectures, input shapes, FL hyperparameters.

Every assigned architecture gets one module in ``repro/configs/`` exporting a
``CONFIG: ArchConfig``; the registry in ``repro/configs/registry.py`` maps
``--arch <id>`` to it.  All configs are frozen dataclasses so they are hashable
and can be closed over by jitted functions safely.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm", "audio"]


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Routed experts with a softmax top-k router (DeepSeekMoE,
    arXiv:2401.06066 / 2405.04434).  The layer may hold a share of the routed
    experts, as one chip of an expert-parallel deployment does: the router
    scores all ``num_experts``, and the layer computes the part of the result
    that its ``held`` experts ``first_held .. first_held + held - 1`` give."""

    num_experts: int               # routed experts the router scores
    top_k: int
    num_shared: int = 0            # shared (always-on) experts
    expert_ff: int = 0             # per-expert FFN hidden dim
    held: int = 0                  # routed experts this layer holds; 0 => all
    first_held: int = 0            # index of the first held expert
    aux_alpha: float = 0.001       # weight of the sequence-wise balance loss

    def held_experts(self) -> int:
        return self.held or self.num_experts


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN rotary scaling as DeepSeek-V2 defines it (arXiv:2309.00071;
    ``rope_scaling`` of the published config)."""

    factor: float = 40.0
    original_max: int = 4096       # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek Multi-head Latent Attention (arXiv:2405.04434 / 2412.19437)."""

    q_lora: int = 0                # 0 => no query compression
    kv_lora: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    yarn: YaRNConfig | None = None  # None => plain rope, softmax scale 1/sqrt(qk)


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 SSD (arXiv:2405.21060)."""

    state_dim: int = 128           # N
    head_dim: int = 64             # P
    num_heads: int = 0             # 0 => derived: expand*d_model/head_dim
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256               # SSD chunk length


@dataclass(frozen=True)
class ArchConfig:
    # identity
    name: str = "unnamed"
    family: Family = "dense"
    citation: str = ""

    # core transformer dims
    n_layers: int = 2              # every layer, the leading dense ones included
    first_dense: int = 0           # leading layers of a moe stack with a dense FFN (d_ff)
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0              # 0 => d_model // n_heads
    d_ff: int = 1024
    vocab: int = 32000

    # attention details
    qkv_bias: bool = False
    rope_kind: Literal["full", "half", "none"] = "full"  # "half" = ChatGLM 2d RoPE
    rope_theta: float = 10000.0
    sliding_window: int = 0        # 0 => full causal attention
    # serving variant: window used when serving long_500k on quadratic archs
    serve_window_long: int = 4096

    # optional feature blocks
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: bool = False           # Hymba parallel attn+SSM heads
    mtp: bool = False              # DeepSeek-V3 multi-token prediction head
    mtp_coef: float = 0.3

    # encoder-decoder (audio) / multimodal stubs
    enc_layers: int = 0            # >0 => encoder-decoder
    src_frames: int = 1024         # audio frontend stub: #frame embeddings
    num_patches: int = 0           # vlm frontend stub: #patch embeddings

    # misc
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # remat ("none" | "full"): checkpoint each layer's activations
    remat: str = "none"
    # unroll factor for the layer scan (dry-run cost-calibration: XLA's
    # HloCostAnalysis counts while-loop bodies once, so unrolled lowerings
    # give exact per-step flops/bytes/collectives)
    scan_unroll: int = 1
    # --- beyond-paper perf switches (EXPERIMENTS.md §Perf; default = baseline)
    opt_banded_window: bool = False   # slice K/V to the sliding-window band
    opt_onehot_xent: bool = False     # gather-free CE picked-logit (sharded vocab)
    opt_seq_shard: bool = False       # sequence-shard the residual stream (TP)

    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(1, self.n_heads))

    def reduced(self, **overrides) -> "ArchConfig":
        """A tiny same-family variant for CPU smoke tests (<=2 layers etc.)."""
        small: dict = dict(
            n_layers=2,
            first_dense=min(self.first_dense, 1),
            d_model=min(self.d_model, 128),
            n_heads=min(self.n_heads, 4),
            d_ff=min(self.d_ff, 256),
            vocab=min(self.vocab, 512),
            head_dim=32 if self.head_dim else 0,
        )
        small["n_kv_heads"] = min(self.n_kv_heads, small["n_heads"])
        if self.moe is not None:
            small["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                num_shared=min(self.moe.num_shared, 1),
                expert_ff=min(self.moe.expert_ff, 128),
                held=0,
                first_held=0,
            )
        if self.mla is not None:
            small["mla"] = dataclasses.replace(
                self.mla,
                q_lora=min(self.mla.q_lora, 64) if self.mla.q_lora else 0,
                kv_lora=min(self.mla.kv_lora, 64),
                qk_nope_dim=32,
                qk_rope_dim=16,
                v_head_dim=32,
            )
        if self.ssm is not None:
            small["ssm"] = dataclasses.replace(
                self.ssm, state_dim=min(self.ssm.state_dim, 16), head_dim=32, num_heads=0, chunk=32
            )
        if self.enc_layers:
            small["enc_layers"] = 2
            small["src_frames"] = 32
        if self.num_patches:
            small["num_patches"] = 16
        if self.sliding_window:
            small["sliding_window"] = 64
        small["dtype"] = "float32"
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


INPUT_SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# FL configuration (the paper's knobs)
# ---------------------------------------------------------------------------

Algorithm = Literal[
    "fedshuffle", "fedavg", "fedavg_so", "fedshuffle_so", "fednova", "fedavg_min",
    "fedavg_mean", "gen",
]
Sampling = Literal["full", "uniform", "independent"]
Aggregation = Literal["unbiased", "sum_one"]
ServerOpt = Literal["sgd", "momentum", "mvr", "adam", "scaffold"]
# Local update chain (repro.fed.strategy.LOCAL_UPDATES; extensible via
# register_local_update, hence plain str):
#   ""           — defer to the server optimizer's paired default
#   "sgd"        — plain RR-SGD (the empty transform chain)
#   "mvr"        — MVR-corrected steps (needs server opt providing "m")
#   "scaffold"   — SCAFFOLD control variates (needs server_opt="scaffold";
#                  keeps a persistent [N, params] state bank)
#   "fedprox"    — proximal term mu*(y - x) (knob: prox_mu)
#   "local_clip" — per-step direction-norm clip (knob: clip_norm)
CohortMode = Literal["vmapped", "sequential"]
Engine = Literal["legacy", "cohort"]
# Round-batch layout the jitted step executes:
#   padded   — one [C, K_max] masked scan for the whole cohort (reference)
#   bucketed — slots partitioned into static step buckets; one [C_b, K_b]
#              scan per bucket, results reassembled in slot order so every
#              aggregate is bitwise-identical to the padded layout
ExecMode = Literal["padded", "bucketed"]
# Where the RR index matrices [C, K_max, B] come from:
#   host        — numpy PCG permutations per cohort client (the seed semantics;
#                 bitwise-identical to the legacy FederatedPipeline path)
#   host_feistel — numpy counter-based swap-or-not permutations (bitwise-equal
#                 to the device backends; useful for cross-checking)
#   device_ref  — stateless swap-or-not generated inside the jitted round (jnp)
#   device      — same math as a Pallas kernel (interpret-mode on CPU)
RRBackend = Literal["host", "host_feistel", "device_ref", "device"]
# Communication plane (repro.fed.comm.CODECS; extensible via register_codec,
# hence plain str).  Codecs register with a direction capability (uplink /
# downlink / both) and each direction resolves its own knob family.
# Uplink (client -> server): clients encode their update inside the jitted
# round and the server decodes-then-combines; non-identity codecs surface
# bytes-on-wire in the round metrics:
#   "identity" — dense uplink (the default; bitwise-frozen no-comm contract)
#   "qsgd"     — stochastic int quantization (uplink_bits levels, one fp32
#                scale per uplink_chunk values; kernels/quantize pack path)
#   "topk"     — magnitude top-k + per-client error feedback (uplink_frac)
#   "randk"    — seeded random-k, unbiased n/k scaling (values-only wire)
#   "ef_qsgd" / "ef_randk" — error-feedback variants
#   "diana_qsgd" / "diana_randk" / "diana_topk" — DIANA-RR learned shifts:
#                each client keeps h_i, ships C(Delta_i - h_i) and both ends
#                apply h_i <- h_i + shift_alpha * C(Delta_i - h_i)
# Downlink (server -> client broadcast): the server encodes the model's
# delta against a client-held reference (banked on ServerState.clients under
# "downlink"); clients reconstruct params = ref + decode(...) inside the
# jitted round and the reconstruction becomes their next reference.
# Downlink-capable codecs are the stateless ones (identity / qsgd / randk) —
# EF/shift state is client-side and uplink-only (register_codec enforces it).
UplinkBackend = Literal["ref", "pallas"]
# Heterogeneous fleet plane (repro.fed.fleet).  Fleet model (FLEETS registry;
# extensible via register_fleet, hence plain str):
#   "homogeneous"  — unit speed, zero latency (with server_mode="sync" and no
#                    faults the fleet plane is fully off — bitwise-frozen)
#   "tiered"       — fleet_tiers discrete device tiers, speeds 1..1/tier_spread
#   "zipf_latency" — Pareto(zipf_alpha)-tailed per-client latency (stragglers)
# Fault scenarios ride FLConfig.faults as a comma-separated list of FAULTS
# registry names ("dropout,straggler,abort"), each with its knobs below.
# Server aggregation mode:
#   "sync"     — the classic synchronous round (the default; frozen contract)
#   "buffered" — FedBuff-style async: cohort_size clients in flight, the
#                server aggregates the first buffer_size arrivals per virtual
#                tick, late updates discounted by the staleness weighting
ServerMode = Literal["sync", "buffered"]
Staleness = Literal["constant", "poly"]
# Observability plane (repro.obs).  "off" (the default) is the frozen
# contract: no new metric keys, bitwise-identical rounds.  "metrics" makes
# the jitted round emit fixed-shape distribution summaries (hist_* keys:
# per-client step counts, update norms, staleness, uplink bytes) and the
# train loop route them into a metric registry; "trace" enables only the
# host span instrumentation (spans still no-op until a tracer is installed
# via obs.trace.capture); "full" = both.
Telemetry = Literal["off", "metrics", "trace", "full"]
# Byzantine-robustness plane (repro.fed.robust).  The defaults (attack="none",
# aggregator="mean", guard="off") keep the plane fully off — bitwise-frozen.
# Attack model (ATTACKS registry; extensible via register_attack, hence plain
# str) — the adversary set is drawn counter-based per (seed, client), attacks
# rewrite the slot-order delta stack before the uplink codec encodes it:
#   "none"         — no adversaries (the frozen default)
#   "sign_flip"    — adversaries ship -attack_scale * Delta_i
#   "zero_update"  — adversaries ship zeros (free-riding)
#   "scaled_noise" — adversaries ship attack_scale * U[-1,1) noise
#   "ipm"          — inner-product manipulation: -attack_scale * honest mean
# Robust aggregator (ROBUST_AGGS registry; register_robust_agg) — weight-aware
# over the strategy's bound FedShuffle coefficients, on the weighted_sum scale:
#   "mean"              — the canonical weighted_sum (the frozen default)
#   "coordinate_median" — per-coordinate weighted median (breakdown 1/2)
#   "trimmed_mean"      — central [trim_frac, 1-trim_frac] mass window
#   "norm_clip"         — clip update norms to the cohort median, then mean
#   "centered_clip"     — iterative centered clipping (Karimireddy et al.)
#   "krum" / "multi_krum" — pairwise-distance selection (Blanchard et al.)
# Self-healing guards:
#   "off"        — no guards (the frozen default)
#   "quarantine" — per-client NaN/Inf/norm-spike quarantine + coefficient
#                  renormalization inside the round
#   "reject"     — server-level divergence guard: revert a blown round's
#                  state updates (the round counter still advances)
#   "full"       — both
Guard = Literal["off", "quarantine", "reject", "full"]
# Privacy plane (repro.fed.privacy).  The defaults (dp="off", secagg="off")
# keep the plane fully off — bitwise-frozen (identical jaxpr, zero new metric
# keys).  DP-FedShuffle mechanism (dp="on"):
#   each shipped client update is L2-clipped to dp_clip (exact sensitivity
#   bound), and Gaussian noise with sigma = dp_noise_mult * dp_clip *
#   max_i |coeff_i| is added in-jit to the weighted aggregate — drawn
#   counter-based per (seed, round) off the rr_perm hash chain, so legacy /
#   engine / prefetch / resumed runs replay identical noise.  The host-side
#   RDP accountant (privacy/accountant.py) converts (dp_noise_mult, the
#   participation schedule's sampling rate, round count) into cumulative
#   eps(dp_delta), reported as the "dp_epsilon" metric.
# Secure-aggregation simulation (secagg="pairwise"):
#   client payloads are fixed-point-encoded (secagg_bits fractional bits,
#   uint32 modular domain — composing with uplink quantization, which runs
#   first) and blinded with seeded pairwise antisymmetric masks
#   (mask(i,j) = -mask(j,i) mod 2^32, keys off the hash chain), so a single
#   wire payload is individually uninformative while masks cancel EXACTLY in
#   the modular sum; fleet-dropped clients' mask shares are reconstructed
#   and subtracted (dropout recovery).  Requires aggregator="mean" and no
#   per-client quarantine guard — the server only ever sees the blinded sum.
DP = Literal["off", "on"]
Secagg = Literal["off", "pairwise"]


@dataclass(frozen=True)
class FLConfig:
    # population
    num_clients: int = 8
    cohort_size: int = 4           # expected #participating clients b
    sampling: Sampling = "uniform"
    # local work
    epochs: int = 1                # E (same for all unless epochs_max > epochs)
    epochs_max: int = 0            # >epochs => E_i ~ U{epochs..epochs_max} per round
    local_batch: int = 1
    k_max: int = 0                 # 0 => derived from data sizes at pipeline build
    # algorithm
    algorithm: Algorithm = "fedshuffle"
    aggregation: Aggregation = "unbiased"
    reshuffle: bool = True         # RR vs with-replacement local sampling
    # step sizes
    local_lr: float = 0.1
    server_lr: float = 1.0
    # server optimizer
    server_opt: ServerOpt = "sgd"
    momentum: float = 0.9          # used by "momentum"
    mvr_a: float = 0.1             # MVR a parameter
    mvr_exact: bool = False        # exact eq.(13-14) vs practical approx (App. F)
    # local client work (composable transform chains; see Literal note above)
    local_update: str = ""         # "" => server opt's paired default
    prox_mu: float = 0.1           # fedprox proximal coefficient
    clip_norm: float = 1.0         # local_clip per-step direction-norm bound
    # distribution
    cohort_mode: CohortMode = "vmapped"
    accum_dtype: str = "float32"   # sequential-mode delta accumulator dtype
    # execution layout (padding-free bucketed scans for imbalanced local work)
    exec_mode: ExecMode = "padded"
    buckets: int = 4               # max step buckets when exec_mode="bucketed"
    # cohort engine (population-scale data plane; repro.fed.cohort)
    engine: Engine = "legacy"      # "cohort" => device-resident data plane
    rr_backend: RRBackend = "host"
    rr_rounds: int = 24            # swap-or-not cipher rounds (device/feistel RR)
    prefetch: int = 2              # rounds sampled ahead by the async scheduler
    participation: str = "iid"     # key into cohort.scheduler.PARTICIPATION
    # communication plane (compressed client->server updates and server->
    # client broadcasts; see the Communication plane note above and
    # repro.fed.comm).  Each direction routes its own knob family through
    # the shared per-direction validator at bind time.
    uplink: str = "identity"       # codec name (key into fed.comm.CODECS)
    uplink_bits: int = 4           # qsgd: bits per value (2 | 4 | 8)
    uplink_chunk: int = 256        # qsgd: values per fp32 scale
    uplink_frac: float = 0.1       # topk/randk: fraction of coords shipped
    uplink_backend: UplinkBackend = "ref"  # quantize pack path, both directions
    shift_alpha: float = 0.5       # diana_*: shift lr, h += alpha * C(d - h)
    # downlink broadcast (reference-compressed; "identity" keeps the dense
    # broadcast bitwise-frozen — the pre-downlink op sequence exactly)
    downlink: str = "identity"     # downlink-capable codec name
    downlink_bits: int = 4         # qsgd: bits per value (2 | 4 | 8)
    downlink_chunk: int = 256      # qsgd: values per fp32 scale
    downlink_frac: float = 0.1     # randk: fraction of coords shipped
    # heterogeneous fleet plane (device tiers, fault injection, async server;
    # see the ServerMode note above and repro.fed.fleet) — the defaults keep
    # the synchronous path bitwise-frozen
    fleet: str = "homogeneous"     # device-tier model (key into fed.fleet.FLEETS)
    fleet_tiers: int = 3           # tiered: number of device speed tiers
    tier_spread: float = 4.0       # tiered: slowest/fastest speed ratio (>= 1)
    tier_latency: float = 1.0      # base per-round latency (virtual-time units)
    zipf_alpha: float = 1.2        # zipf_latency: Pareto tail exponent
    faults: str = ""               # comma-separated fed.fleet.FAULTS scenarios
    drop_prob: float = 0.0         # "dropout": per-(client, round) failure prob
    straggler_prob: float = 0.0    # "straggler": P(round slowed by the factor)
    straggler_factor: float = 8.0  # "straggler": wall-time multiplier (>= 1)
    round_deadline: float = 0.0    # "abort": virtual-time budget cutting steps
    server_mode: ServerMode = "sync"
    buffer_size: int = 16          # buffered: aggregate first K arrivals/tick
    staleness: Staleness = "poly"  # buffered staleness discount kind
    staleness_power: float = 0.5   # poly: weight = (1 + tau) ** -staleness_power
    # observability plane (span tracing + metric registry + in-jit
    # histograms; see the Telemetry note above and repro.obs) — "off" keeps
    # every existing configuration bitwise-frozen
    telemetry: Telemetry = "off"
    telemetry_bins: int = 16       # bins per in-jit histogram (static shapes)
    # byzantine-robustness plane (adversarial clients, robust aggregation,
    # self-healing guards; see the Attack/Aggregator/Guard notes above and
    # repro.fed.robust) — the defaults keep the plane bitwise-frozen off
    attack: str = "none"           # adversary model (key into robust.ATTACKS)
    attack_frac: float = 0.0       # expected adversarial fraction of clients
    attack_scale: float = 1.0      # attack magnitude multiplier
    aggregator: str = "mean"       # server combiner (key into robust.ROBUST_AGGS)
    trim_frac: float = 0.1         # trimmed_mean/krum breakdown parameter (0, 0.5)
    guard: Guard = "off"           # self-healing guards (quarantine/reject/full)
    # privacy plane (per-client DP clipping + server Gaussian noise + RDP
    # accountant + secure-aggregation simulation; see the DP/Secagg note
    # above and repro.fed.privacy) — the defaults keep the plane bitwise-
    # frozen off
    dp: DP = "off"                 # DP-FedShuffle mechanism (clip + noise + eps)
    dp_clip: float = 1.0           # per-update L2 clip bound (DP sensitivity C)
    dp_noise_mult: float = 1.0     # noise multiplier z: sigma = z * sensitivity
    dp_delta: float = 1e-5         # target delta for the eps(delta) report
    secagg: Secagg = "off"         # pairwise-mask secure-aggregation simulation
    secagg_bits: int = 16          # fixed-point fractional bits (1..30)
    # system heterogeneity (Fig. 4): every client is cut short by this many
    # local steps (planned vs actual); the "gen" hybrid algorithm corrects it
    drop_last_steps: int = 0
    # data imbalance
    imbalance: Literal["equal", "lognormal", "zipf"] = "lognormal"
    min_samples: int = 2
    mean_samples: int = 8
    seed: int = 0


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")


@dataclass(frozen=True)
class RunConfig:
    arch: ArchConfig = field(default_factory=ArchConfig)
    shape: ShapeConfig = field(default_factory=lambda: INPUT_SHAPES["train_4k"])
    fl: FLConfig = field(default_factory=FLConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
