"""Typed configuration objects.

The reference threads a raw argparse ``args`` namespace everywhere
(``/root/reference/utils.py:33,80``) with 10 flags (``/root/reference/main.py:30-49``)
and a module-level ``max_token_len = 4096`` constant (``/root/reference/utils.py:14``).
Here the same flag surface becomes a small frozen dataclass, plus a model config
read from a HuggingFace ``config.json``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

# The reference's hard sequence cap (/root/reference/utils.py:14). Kept as the
# default, but configurable here instead of a module constant.
DEFAULT_MAX_TOKEN_LEN = 4096

# MLP gate activations models/llama.py implements (its _ACT table asserts it
# stays in sync with this set).
SUPPORTED_ACTIVATIONS = frozenset({"silu", "gelu", "gelu_pytorch_tanh"})

# Named fault-injection sites (faults/inject.py fires these; config
# validation and the --chaos CLI flag key off this tuple so a typo'd site
# fails loudly instead of silently injecting nothing). Machine-checked by
# flscheck's SITE-REG rule (analysis/rules.py): every literal fired in the
# package must be registered here AND documented in docs/faults.md's site
# table, and every entry here must actually be fired somewhere. The corrupt_* sites
# are SILENT-corruption sites: instead of raising, they bit-flip (or
# truncate) the bytes mid-flight — what the integrity layer's checksums
# exist to catch (corrupt_shard: one layer file's loaded tensors;
# corrupt_activation: one .npy spill read). The replica_* sites are
# REPLICA-level (serve/fleet.py, fired once per shard step of every
# replica's sweep): replica_kill crashes a whole serving engine mid-sweep
# (engine-fatal, modeling a dead replica process), replica_stall wedges
# its thread until the fleet's liveness check declares it dead — both
# exist to prove the router's hard-fail + exactly-once re-dispatch path.
# The RESOURCE-PRESSURE sites model the three exhaustion paths the
# architecture leans on hardest (runtime/pressure.py, docs/pressure.md):
# host_oom raises MemoryError inside a host shard build (typed to
# HostOOMError and retried like any transient I/O blip), disk_full raises
# ENOSPC inside an activation-spill write (typed DiskFullError, same
# retry ladder), link_throttle stalls a host->HBM put for latency_s —
# a saturated link slows, it never errors.
FAULT_SITES = (
    "shard_read",
    "device_put",
    "engine_step",
    "queue_admission",
    "corrupt_shard",
    "corrupt_activation",
    "replica_kill",
    "replica_stall",
    "host_oom",
    "disk_full",
    "link_throttle",
)


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Deterministic fault injection (faults/inject.py). Off by default;
    enabled by the chaos tests and the ``--chaos`` CLI flag.

    Rates partition one uniform draw per site fire: with probability
    ``error_rate`` an IOError is raised, ``truncate_rate`` a simulated
    truncated read, ``latency_rate`` a ``latency_s`` sleep; otherwise the
    fire is clean. The schedule is a pure function of ``(seed, site,
    per-site call count)`` — reproducible across runs, platforms, and
    thread interleavings."""

    enabled: bool = False
    seed: int = 0
    error_rate: float = 0.0
    truncate_rate: float = 0.0
    latency_rate: float = 0.0
    latency_s: float = 0.01
    sites: tuple[str, ...] = ()  # () = every site
    # Total faults injected before the schedule goes permanently clean
    # (-1 = unlimited). Models a transient outage that ENDS — lets a test
    # force exactly one retry-exhaustion and then assert clean recovery.
    max_faults: int = -1

    def __post_init__(self) -> None:
        for name in ("error_rate", "truncate_rate", "latency_rate"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v}")
        # 1e-9 slack: a legal decimal partition like 0.1+0.2+0.7 sums to
        # 1.0000000000000002 in IEEE-754 and must not be rejected.
        if self.error_rate + self.truncate_rate + self.latency_rate > 1.0 + 1e-9:
            raise ValueError("fault rates must sum to <= 1")
        if self.latency_s < 0:
            raise ValueError("latency_s must be >= 0")
        unknown = set(self.sites) - set(FAULT_SITES)
        if unknown:
            raise ValueError(
                f"unknown fault sites {sorted(unknown)} (one of {FAULT_SITES})"
            )
        object.__setattr__(self, "sites", tuple(self.sites))


@dataclasses.dataclass(frozen=True)
class PressureConfig:
    """Resource-pressure brownout controller (runtime/pressure.py). Off by
    default; enabled by ``--pressure`` on both CLIs.

    A ``PressureMonitor`` samples host MemAvailable, spill-disk free
    bytes, HBM headroom, and the host->HBM link rate every ``poll_s``;
    when a threshold trips (or a hard resource failure — a real or
    injected ``host_oom``/``disk_full`` event — is observed), the
    ``BrownoutController`` walks an ordered, REVERSIBLE degradation
    ladder: shrink the host shard cache, evict residency pins back to
    streaming, shed new admissions with a typed ``Overloaded`` rejection
    (carrying ``shed_retry_after_s`` as the retry hint), and drain fleet
    replicas — then steps back down once ``step_down_polls`` consecutive
    polls come back clean. Thresholds set to 0 disable that signal
    (events still drive the ladder)."""

    enabled: bool = False
    poll_s: float = 1.0
    # Signal thresholds (0 = that signal off; unknown samples never trip).
    host_min_gb: float = 1.0      # MemAvailable floor
    disk_min_gb: float = 1.0      # spill-disk (disk_folder) free-bytes floor
    hbm_headroom_frac: float = 0.05  # device free/limit floor
    link_min_gbps: float = 0.0    # host->HBM streamed-bytes rate floor
    # Ladder behavior.
    cache_shrink_frac: float = 0.5   # level-1 host-cache budget multiplier
    shed_retry_after_s: float = 1.0  # Overloaded.retry_after_s hint
    step_down_polls: int = 3         # consecutive clean polls per step down

    def __post_init__(self) -> None:
        if self.poll_s <= 0:
            raise ValueError("poll_s must be > 0")
        for name in ("host_min_gb", "disk_min_gb", "link_min_gbps",
                     "shed_retry_after_s"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0")
        for name in ("hbm_headroom_frac", "cache_shrink_frac"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v}")
        if self.step_down_polls < 1:
            raise ValueError("step_down_polls must be >= 1")


@dataclasses.dataclass(frozen=True)
class AdapterConfig:
    """Multi-tenant LoRA adapter serving (adapters/; docs/adapters.md).

    ``dir`` is a directory of named adapters — one subdirectory per
    adapter, each holding per-layer safetensors delta factors plus an
    ``adapter_plan.json`` (the PR 14 plan shape) and an integrity
    manifest. Empty (default) disables the subsystem entirely: requests
    carrying an ``adapter_id`` are rejected and the sweep math is
    byte-identical to a tree without adapters. ``max_gb`` budgets the
    host-resident adapter LRU (``adapters/loader.py``): an explicit
    number of GB, or None (auto) for a small fraction of available RAM —
    auto stays ON under fault injection (chaos-exempt like the KV pool:
    the chaos smoke serves adapters *under* faults, so the budget must
    not silently vanish there)."""

    dir: str = ""
    max_gb: float | None = None

    def __post_init__(self) -> None:
        if self.max_gb is not None and self.max_gb < 0:
            raise ValueError(
                f"max_gb must be >= 0 (or None for auto), got {self.max_gb}"
            )


# Multimodal wrapper model types -> their language-model type. Published
# Gemma-3 / Llama-4 checkpoints are vision+text bundles whose config nests
# the text model under "text_config"; both the config parse and the
# checkpoint splitter derive the text model through extract_text_config —
# ONE rule, so the two can't drift.
MULTIMODAL_TEXT_TYPES = {"gemma3": "gemma3_text", "llama4": "llama4_text"}


def extract_text_config(d: dict) -> dict | None:
    """The normalized language-model config dict of a multimodal wrapper
    config, or None when ``d`` is not a wrapper. Raises ValueError for a
    wrapper with no text_config."""
    text_type = MULTIMODAL_TEXT_TYPES.get(d.get("model_type"))
    if text_type is None:
        return None
    tc = d.get("text_config")
    if not tc:
        raise ValueError(
            f"{d.get('model_type')} config without text_config — cannot "
            "derive the language model"
        )
    tc = dict(tc)
    tc.setdefault("model_type", text_type)
    return tc

# Fields copied by name from ANY foreign HF config.json — they mean the same
# thing across the supported families. Everything else is family-gated below
# (see from_hf_config's stray-key defence).
_UNIVERSAL_HF_FIELDS = frozenset({
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "rms_norm_eps", "rope_theta", "max_position_embeddings",
    "tie_word_embeddings", "hidden_act", "mlp_bias",
})

# Extra fields a foreign config.json may contribute, per declared model_type
# (these are real HF config attributes for that family; the family branch
# supplies the defaults when absent).
_FAMILY_HF_FIELDS: dict[str, frozenset[str]] = {
    "mistral": frozenset({"sliding_window"}),
    "qwen2": frozenset({"sliding_window"}),
    "qwen3": frozenset({"sliding_window"}),
    "qwen3_moe": frozenset(
        {"sliding_window", "num_local_experts", "num_experts_per_tok"}
    ),
    "mixtral": frozenset(
        {"sliding_window", "num_local_experts", "num_experts_per_tok"}
    ),
    "phi3": frozenset({"sliding_window"}),
    "gemma2": frozenset({"query_pre_attn_scalar", "sliding_window"}),
    "gemma3_text": frozenset(
        {"query_pre_attn_scalar", "sliding_window", "rope_local_theta"}
    ),
    "llama4_text": frozenset(
        {
            "num_local_experts",
            "num_experts_per_tok",
            "attention_chunk_size",
            "intermediate_size_mlp",
            "attn_temperature_tuning",
        }
    ),
    "deepseek_v3": frozenset(
        {
            "kv_lora_rank",
            "q_lora_rank",
            "qk_nope_head_dim",
            "qk_rope_head_dim",
            "v_head_dim",
            "num_experts_per_tok",
        }
    ),
}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Model hyperparameters, mirroring the fields of a HF config.json.

    Covers the Llama *family* of decoder architectures: Llama-1/2/3 (the
    reference's only model, ``/root/reference/utils.py:101,110``), plus the
    Llama-shaped variants the same streaming machinery runs unchanged —
    Mistral (sliding-window attention) and Qwen2 (biased Q/K/V projections).
    The family differences are data, not code paths: bias flags and an
    optional attention window, all static jit args.
    """

    # 'llama' | 'mistral' | 'qwen2' | 'qwen3' | 'mixtral' | 'gemma'
    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    explicit_head_dim: int | None = None  # HF 'head_dim' when != hidden/heads
    # Projection biases. Llama's HF config drives all four attention
    # projections from one 'attention_bias' flag; Qwen2 hard-codes bias on
    # q/k/v but none on o_proj, hence the split here.
    attention_in_bias: bool = False  # bias on wq/wk/wv
    attention_out_bias: bool = False  # bias on wo
    mlp_bias: bool = False  # bias on gate/up/down
    # Sliding-window attention (Mistral; Qwen2 with use_sliding_window).
    # None = full causal. Semantics match HF masking_utils: query i attends
    # key j iff j <= i and i - j < sliding_window.
    sliding_window: int | None = None
    # Mixture-of-experts MLP (Mixtral / Qwen3-MoE). 0 = dense. Routing
    # matches HF: softmax over all experts (fp32) -> top-k -> renormalise
    # (iff moe_norm_topk_prob; HF calls it norm_topk_prob and it is the
    # ONLY difference between the Mixtral and Qwen3-MoE blocks) -> combine.
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    moe_norm_topk_prob: bool = True
    # Per-head-dim RMSNorm on q/k after the head reshape, before RoPE
    # (Qwen3; HF: 'unlike olmo, only on the head dim').
    qk_norm: bool = False
    # MLP gate activation. 'silu' (llama/mistral/qwen/mixtral),
    # 'gelu_pytorch_tanh' (gemma), 'gelu' (exact erf).
    hidden_act: str = "silu"
    # Gemma conventions: RMSNorm multiplies by (1 + weight) IN FLOAT32
    # before the downcast (HF PR #29402 — the cast order is quality-
    # relevant at bf16), and embeddings are scaled by sqrt(hidden_size)
    # (the normalizer itself rounded to the compute dtype, per HF).
    norm_unit_offset: bool = False
    embed_scale: bool = False
    # Gemma2 additions. ffw_sandwich_norms: post_attention_layernorm moves
    # to the attention OUTPUT (before the residual add) and the MLP gets
    # pre/post_feedforward_layernorms. Softcaps apply soft*tanh(x/soft) to
    # attention scores (pre-mask) / final logits. query_pre_attn_scalar
    # replaces head_dim in the attention scale when set. layer_sliding
    # toggles the sliding window PER LAYER (True = sliding) — Gemma2
    # alternates, layer_types-derived; None = uniform per sliding_window.
    ffw_sandwich_norms: bool = False
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    query_pre_attn_scalar: float | None = None
    layer_sliding: tuple[bool, ...] | None = None
    # Gemma3: sliding (local) layers use this UNSCALED rope base while full
    # (global) layers use rope_theta + rope_scaling. None = single base.
    rope_local_theta: float | None = None
    # Llama4 additions. Chunked attention: local layers (layer_sliding=True)
    # attend within position chunks of this size instead of a sliding
    # window (mutually exclusive with sliding_window). layer_rope: per-layer
    # rope on/off (NoPE global layers). qk_l2_norm: weightless L2 norm on
    # q/k AFTER rope, rope layers only. attn_temperature_tuning: NoPE-layer
    # queries scale by log(floor((pos+1)/floor)+1)*coef + 1. moe_layer
    # pattern: True = that layer's MLP is the (shared + routed top-k
    # sigmoid-input-scaled) MoE; dense llama4 layers use
    # intermediate_size_mlp.
    attention_chunk_size: int | None = None
    layer_rope: tuple[bool, ...] | None = None
    rope_interleaved: bool = False  # llama4 complex-pair rotation
    qk_l2_norm: bool = False
    attn_temperature_tuning: bool = False
    attn_floor_scale: float = 8192.0
    attn_scale_coef: float = 0.1
    # Descriptive round-trip metadata: the runtime derives MoE-vs-dense
    # structure and the dense width from the checkpoint's weight keys/shapes
    # (the files are ground truth); these record the pattern for tooling.
    moe_layer_pattern: tuple[bool, ...] | None = None
    intermediate_size_mlp: int | None = None

    def __post_init__(self) -> None:
        if self.sliding_window is not None and self.attention_chunk_size is not None:
            # The attention ops implement exactly one local form per model;
            # both set would make the monolithic and streaming paths mask
            # differently instead of failing loudly.
            raise ValueError(
                "sliding_window and attention_chunk_size are mutually exclusive"
            )
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps must be >= 1, got {self.total_ut_steps}"
            )

    @property
    def attn_scale(self) -> float:
        base = (
            self.query_pre_attn_scalar
            if self.query_pre_attn_scalar is not None
            else self.head_dim
        )
        return float(base) ** -0.5
    # RoPE scaling, flattened to hashable fields (the config must stay a
    # frozen/hashable jit static arg): kind None = unscaled, or
    # 'linear' (Llama-2 long) / 'llama3' (Llama-3.1+ frequency bands) /
    # 'yarn' (NTK-by-parts: Qwen2.5-long / DeepSeek-style checkpoints).
    rope_scaling_kind: str | None = None
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # yarn-only: ramp boundaries, the cos/sin attention factor (resolved at
    # parse time from attention_factor / mscale / mscale_all_dim / factor),
    # and whether the correction range truncates to whole dims (HF default).
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    rope_truncate: bool = True
    # longrope-only (Phi-3 long-context): per-frequency-band extension
    # factors, head_dim//2 entries each. The long/short choice is made at
    # runtime from the sequence's real length vs rope_original_max_position
    # (ops/rope.py rope_cos_sin).
    rope_long_factor: tuple | None = None
    rope_short_factor: tuple | None = None
    # Multi-head latent attention (DeepSeek-V2/V3, model_type deepseek_v3).
    # kv_lora_rank > 0 switches the q/k/v assembly (models/llama.py
    # _qkv_mla): queries optionally LoRA'd (q_lora_rank; None = dense
    # q_proj), KV compressed to kv_lora_rank + one SHARED qk_rope_head_dim
    # rope key, decompressed per head to qk_nope_head_dim keys and
    # v_head_dim values. head_dim (qk) = qk_nope + qk_rope; values keep
    # their own v_head_dim.
    kv_lora_rank: int = 0
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int | None = None
    # DeepSeek MoE routing deltas vs Mixtral (models/llama.py
    # _deepseek_moe_mlp): sigmoid scores, selection biased by a trained
    # correction buffer (weights stay unbiased), group-limited top-k
    # (n_group groups scored by their top-2 sum, best topk_group groups
    # kept), x routed_scaling_factor, plus a shared expert of
    # n_shared_experts x the routed width.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scaling_factor: float = 1.0
    # DeepSeek shared-expert width multiplier: the shared expert is ONE MLP
    # of n_shared_experts x the routed width (V3: 1; V2/V2-Lite: 2). Forward
    # passes take the width from the checkpoint's own shapes; this field
    # keeps the analytic param/FLOPs accounting (utils/metrics.py) and
    # init_mixed_params consistent with it.
    n_shared_experts: int = 1
    # Expert parallelism's share of an expert layer (the router keeps its
    # num_local_experts outputs and its top-k; this process holds the
    # experts ``moe_ep_rank * E/moe_ep_size ...`` and computes their part of
    # the layer's result: models/llama.py _deepseek_moe_mlp). 1 = all held.
    moe_ep_size: int = 1
    moe_ep_rank: int = 0
    # Two attention shapes in one model (MiMo-V2: window layers carry other
    # KV-head counts than full layers). (heads, kv heads, qk dim, v dim) of
    # the LOCAL (layer_sliding) layers; None = the model's one shape. The
    # layer functions take a layer's kind from the weights they are given.
    local_attn_shape: tuple[int, int, int, int] | None = None
    # Rotary on the first rotary_dim dims of a head only (None = all of it).
    rotary_dim: int | None = None
    # Values scaled by this factor before attention (MiMo-V2).
    attn_value_scale: float | None = None
    # A learned per-head sink logit in the softmax's denominator, in the
    # local / the global layers (the weights' ``attn.sink`` leaf is what the
    # forward passes go by; these say which layers a checkpoint has it in).
    attn_sink_local: bool = False
    attn_sink_global: bool = False
    # Linear-attention layers beside softmax ones (MiniCPM-SALA's
    # ``lightning-attn``): layer_linear[i] True = layer i keeps a decayed
    # recurrent state [heads, qk dim, v dim] instead of keys and values
    # (ops/lightning_attention.py), at linear_attn_shape = (heads, kv heads,
    # qk dim, v dim), with a per-head RMSNorm on its output
    # (linear_output_norm) and a sigmoid gate from the layer's input on it
    # (linear_output_gate; attn_output_gate: the same gate on the softmax
    # layers). As everywhere, the forward passes go by the weights they are
    # given (``attn.o_norm``, ``attn.wg``); these say what a checkpoint has.
    layer_linear: tuple[bool, ...] | None = None
    linear_attn_shape: tuple[int, int, int, int] | None = None
    linear_output_norm: bool = False
    linear_output_gate: bool = False
    attn_output_gate: bool = False
    # From this many tokens on the model's softmax layers select blocks of
    # keys (learned block-sparse attention), which no path here computes:
    # such a prompt is refused, never run dense. None = always dense.
    sparse_attn_from: int | None = None
    # MiniCPM's muP scalings: embeddings times embed_multiplier, every
    # sublayer's output times residual_multiplier before its residual add,
    # the final norm's output over logit_divisor before the head.
    embed_multiplier: float | None = None
    residual_multiplier: float | None = None
    logit_divisor: float | None = None
    # A looped (universal-transformer) stack (Ouro): every token visits the
    # SAME decoder layers total_ut_steps times; the final norm closes every
    # step (its output feeds the next) and an exit gate beside it (the norm
    # file's ``gate`` leaves) gives each scored token a probability of
    # stopping there. early_exit_threshold q: a scored token reads the first
    # step at which the cumulative exit probability reaches q; q >= 1 (the
    # published value) reads the last step. 1 = every other model.
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # Which linear attention ``layer_linear`` layers run: ``lightning`` (a
    # decayed sum, one rate a head: ops/lightning_attention.py) or ``kda``
    # (GLM-5.3's delta-rule state with a decay per key channel and token,
    # behind a causal depthwise convolution of ``linear_conv_size`` taps on
    # q, k and v; the decay's log is ``linear_gate_lower_bound * sigmoid(.)``,
    # which bounds it from below: ops/kda_attention.py).
    linear_kind: str = "lightning"
    linear_conv_size: int = 0
    linear_gate_lower_bound: float = 0.0
    # Manifold-constrained hyper-connections (mHC): the residual between
    # layers is ``hc_mult`` streams of ``hidden_size``, a row ``hc_mult *
    # hidden_size`` wide; each sublayer reads one mix of them and writes back
    # through a Sinkhorn-normalised (``hc_sinkhorn_iters`` rounds) stream-
    # mixing matrix (models/llama.py ``_hc_pre`` / ``_hc_post``). 1 = the
    # plain ``x + y`` residual of every other model.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    # SwiGLU clamp: ``silu(min(gate, L)) * clip(up, -L, L)`` in every MLP
    # and expert. None = unclamped.
    swiglu_limit: float | None = None

    def attn_shape(self, sliding: bool = False, linear: bool = False) -> tuple[int, int, int, int]:
        """(heads, kv heads, qk head dim, v head dim) of a layer kind."""
        if linear and self.linear_attn_shape is not None:
            return self.linear_attn_shape
        if sliding and self.local_attn_shape is not None:
            return self.local_attn_shape
        return (
            self.num_attention_heads, self.num_key_value_heads,
            self.head_dim, self.v_dim,
        )

    def require_one_attention_shape(self, path: str, layer_fn: bool = False) -> None:
        """Fail loudly on a path that assumes pages of keys and values of ONE
        shape: it sizes its KV state or shards its heads from
        ``num_key_value_heads`` alone. Refused: a model with linear-attention
        layers (their state is no KV: nothing pools, pages, snapshots or
        shards it yet), and, unless ``layer_fn`` (the layer functions, which
        take a layer's shape from its weights), a model whose layer kinds
        differ in attention shape or that holds a share of its experts. Only
        the streamed scoring path carries those."""
        if self.layer_linear is not None:
            raise NotImplementedError(
                f"{path} does not support {self.model_type}: a linear-"
                "attention layer's recurrent state is not a KV cache; such a "
                "model runs on the streamed scoring path only"
            )
        if layer_fn:
            return
        if self.local_attn_shape is not None or self.moe_ep_size > 1:
            raise NotImplementedError(
                f"{path} does not support {self.model_type}: per-kind "
                "attention shapes and a held share of the experts run on the "
                "streamed scoring path only"
            )

    def require_single_visit(self, path: str) -> None:
        """Fail loudly on a path that keeps its state BY LAYER (pages of
        keys and values, a pipeline stage, a head shard, a gradient): a
        looped model (``total_ut_steps`` > 1) visits each layer several
        times a token with keys and values of that step only, so it needs
        its state by (step, layer), which only the streamed scoring path's
        plan of visits has (``parallel/planner.py``)."""
        if self.total_ut_steps > 1:
            raise NotImplementedError(
                f"{path} does not support {self.model_type}: total_ut_steps="
                f"{self.total_ut_steps} visits every layer that many times "
                "and needs state by (step, layer); such a model runs on the "
                "streamed scoring path only (single executor or DP)"
            )

    @property
    def held_experts(self) -> range:
        """Ids of the routed experts this process holds."""
        n = self.num_local_experts // self.moe_ep_size
        return range(self.moe_ep_rank * n, (self.moe_ep_rank + 1) * n)

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:  # MLA: the qk head dim
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.explicit_head_dim is not None:
            return self.explicit_head_dim
        return self.hidden_size // self.num_attention_heads

    @property
    def v_dim(self) -> int:
        """Value head dim — equals head_dim except under MLA."""
        return self.v_head_dim if self.v_head_dim is not None else self.head_dim

    @property
    def rope_scaling_spec(self) -> tuple | None:
        """Hashable spec consumed by ops.rope.rope_cos_sin."""
        if self.rope_scaling_kind is None:
            return None
        if self.rope_scaling_kind == "linear":
            return ("linear", self.rope_scaling_factor)
        if self.rope_scaling_kind == "yarn":
            return (
                "yarn",
                self.rope_scaling_factor,
                self.rope_beta_fast,
                self.rope_beta_slow,
                self.rope_original_max_position,
                self.rope_attention_factor,
                self.rope_truncate,
            )
        if self.rope_scaling_kind == "longrope":
            return (
                "longrope",
                self.rope_long_factor,
                self.rope_short_factor,
                self.rope_original_max_position,
                self.rope_attention_factor,
            )
        return (
            "llama3",
            self.rope_scaling_factor,
            self.rope_low_freq_factor,
            self.rope_high_freq_factor,
            self.rope_original_max_position,
        )

    @staticmethod
    def _sliding_pattern(
        d: dict[str, Any], family: str, default_fn, token: str = "sliding_attention"
    ) -> tuple[bool, ...]:
        """Per-layer local-attention flags from ``layer_types`` (validated
        against num_hidden_layers) or the family's derivation rule
        ``default_fn(i, n)``. ``token`` is the layer_types value meaning
        "local" (llama4 uses 'chunked_attention')."""
        # 32 = this dataclass's num_hidden_layers default, so a derived
        # pattern always matches the constructed config's layer count.
        n = d.get("num_hidden_layers", 32)
        lt = d.get("layer_types")
        pattern = (
            tuple(t == token for t in lt)
            if lt
            else tuple(bool(default_fn(i, n)) for i in range(n))
        )
        if len(pattern) != n:
            raise ValueError(
                f"{family} layer_types has {len(pattern)} entries for {n} layers"
            )
        return pattern

    @classmethod
    def _apply_sliding_pattern(
        cls, kwargs: dict[str, Any], d: dict[str, Any], family: str, default_fn,
        default_window: int,
    ) -> None:
        """Fold a per-layer pattern into (sliding_window, layer_sliding):
        all-off -> window None; all-on -> uniform window; mixed -> flags.
        An explicit native layer_sliding key wins untouched."""
        if "layer_sliding" in kwargs:
            return
        pattern = cls._sliding_pattern(d, family, default_fn)
        kwargs.setdefault("sliding_window", default_window)
        if not any(pattern):
            kwargs["sliding_window"] = None
        elif not all(pattern):
            kwargs["layer_sliding"] = pattern

    @classmethod
    def _apply_qwen_window(cls, kwargs: dict[str, Any], d: dict[str, Any]) -> None:
        """HF qwen2/qwen3: window active only under use_sliding_window; layer
        i slides iff i >= max_window_layers (class default 28), or per the
        explicit layer_types list. Both HF config classes default
        sliding_window to 4096."""
        if "layer_sliding" in kwargs:  # explicit native key wins
            return
        if not d.get("use_sliding_window", False):
            kwargs["sliding_window"] = None
            return
        mwl = d.get("max_window_layers", 28)
        cls._apply_sliding_pattern(kwargs, d, "qwen", lambda i, n: i >= mwl, 4096)

    @staticmethod
    def _apply_mimo_v2(kwargs: dict[str, Any], d: dict[str, Any]) -> None:
        """MiMo-V2-Flash (``mimo_v2_flash``): full and sliding-window layers
        of different attention shapes in one model (``hybrid_layer_pattern``:
        1 = window), each kind with its own KV-head count and rope base;
        qk and v head dims differ without MLA; rotary on the leading
        ``partial_rotary_factor`` share of a head; values scaled; a learned
        sink logit per head in the window layers' softmax; the DeepSeek
        router (sigmoid, ``noaux_tc`` bias) over experts with no shared one.
        Width convention as deepseek_v3: intermediate_size = the expert
        width, intermediate_size_mlp = the dense layers'."""
        n = int(d.get("num_hidden_layers", 32))
        hd = int(d.get("head_dim", 192))
        vd = int(d.get("v_head_dim", hd))
        kwargs["explicit_head_dim"] = hd
        kwargs["v_head_dim"] = vd
        kwargs["rms_norm_eps"] = float(d.get("layernorm_epsilon", 1e-5))
        kwargs["rotary_dim"] = int(hd * float(d.get("partial_rotary_factor", 1.0)))
        if kwargs["rotary_dim"] % 2:
            raise ValueError(f"mimo_v2_flash rotary dim {kwargs['rotary_dim']} is odd")
        avs = d.get("attention_value_scale")
        kwargs["attn_value_scale"] = None if avs is None else float(avs)
        if d.get("attention_bias"):
            kwargs["attention_in_bias"] = kwargs["attention_out_bias"] = True
        pattern = tuple(bool(x) for x in d.get("hybrid_layer_pattern") or [0] * n)
        if len(pattern) != n:
            raise ValueError(
                f"mimo_v2_flash hybrid_layer_pattern has {len(pattern)} entries "
                f"for {n} layers"
            )
        kwargs["sliding_window"] = d.get("sliding_window") if any(pattern) else None
        kwargs["layer_sliding"] = pattern if any(pattern) and not all(pattern) else None
        kwargs["rope_local_theta"] = float(d.get("swa_rope_theta", 10000.0))
        nq = int(d.get("num_attention_heads", 32))
        kwargs["local_attn_shape"] = (
            int(d.get("swa_num_attention_heads", nq)),
            int(d.get("swa_num_key_value_heads", d.get("num_key_value_heads", nq))),
            int(d.get("swa_head_dim", hd)),
            int(d.get("swa_v_head_dim", vd)),
        )
        kwargs["attn_sink_local"] = bool(d.get("add_swa_attention_sink_bias", False))
        kwargs["attn_sink_global"] = bool(d.get("add_full_attention_sink_bias", False))
        n_routed = int(d.get("n_routed_experts") or 0)
        kwargs["num_local_experts"] = n_routed
        if not n_routed:
            return
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"mimo_v2_flash scoring_func {d.get('scoring_func')!r} (sigmoid is supported)"
            )
        kwargs["intermediate_size_mlp"] = int(d.get("intermediate_size", 11008))
        kwargs["intermediate_size"] = int(d.get("moe_intermediate_size", 2048))
        kwargs["num_experts_per_tok"] = int(d.get("num_experts_per_tok", 8))
        kwargs["moe_norm_topk_prob"] = bool(d.get("norm_topk_prob", True))
        kwargs["moe_n_group"] = int(d.get("n_group") or 1)
        kwargs["moe_topk_group"] = int(d.get("topk_group") or 1)
        rsf = d.get("routed_scaling_factor")
        kwargs["moe_routed_scaling_factor"] = 1.0 if rsf is None else float(rsf)
        kwargs["n_shared_experts"] = int(d.get("n_shared_experts") or 0)  # null = none
        freq = d.get("moe_layer_freq", 1)
        moe = tuple(bool(x) for x in freq) if isinstance(freq, (list, tuple)) else (
            tuple(i % int(freq) == 0 for i in range(n))
        )
        if len(moe) != n:
            raise ValueError(
                f"mimo_v2_flash moe_layer_freq has {len(moe)} entries for {n} layers"
            )
        if not all(moe):
            kwargs["moe_layer_pattern"] = moe
        # Which experts this process holds comes from the model's own
        # config.json: the share of rank ep_rank among ep_size.
        ep, rank = int(d.get("ep_size") or 1), int(d.get("ep_rank") or 0)
        if n_routed % ep or not 0 <= rank < ep:
            raise ValueError(
                f"mimo_v2_flash: {n_routed} experts do not split over ep_size {ep} "
                f"(ep_rank {rank})"
            )
        kwargs["moe_ep_size"], kwargs["moe_ep_rank"] = ep, rank

    @staticmethod
    def _apply_minicpm_sala(kwargs: dict[str, Any], d: dict[str, Any]) -> None:
        """MiniCPM-SALA (``minicpm_sala``): ``mixer_types`` lists each layer's
        kind, ``lightning-attn`` (linear attention with a per-head decay; the
        ``lightning_*`` keys give its shape and whether it rotates) or
        ``minicpm4`` (grouped-query softmax attention, rotary only under
        ``attn_use_rope``; its learned block-sparse selection starts at
        ``sparse_config.dense_len`` tokens, MiniCPM4's published 8192 where
        the config carries none, and is refused from there); a per-head q/k
        norm and an output gate on both kinds, an output norm on the linear
        one; MiniCPM's muP scalings."""
        n = int(d.get("num_hidden_layers", 32))
        mixers = list(d.get("mixer_types") or ["minicpm4"] * n)
        unknown = sorted(set(mixers) - {"minicpm4", "lightning-attn"})
        if len(mixers) != n or unknown:
            raise ValueError(
                f"minicpm_sala mixer_types has {len(mixers)} entries for {n} "
                f"layers (unknown kinds: {unknown})"
            )
        linear = tuple(m == "lightning-attn" for m in mixers)
        nq = int(d.get("num_attention_heads", 32))
        hd = int(d.get("head_dim") or d.get("hidden_size", 4096) // nq)
        kwargs["qk_norm"] = bool(d.get("qk_norm", False))
        kwargs["attn_output_gate"] = bool(d.get("attn_use_output_gate", False))
        kwargs["sliding_window"] = None
        if any(linear):
            lh, ld = int(d.get("lightning_nh", nq)), int(d.get("lightning_head_dim", hd))
            if int(d.get("lightning_nkv", lh)) != lh:
                raise NotImplementedError(
                    "minicpm_sala: lightning_nkv != lightning_nh (grouped "
                    "linear attention) is not supported"
                )
            if d.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
                raise NotImplementedError(
                    f"minicpm_sala lightning_scale {d.get('lightning_scale')!r} "
                    "('1/sqrt(d)' is supported)"
                )
            kwargs["layer_linear"] = linear
            kwargs["linear_attn_shape"] = (lh, lh, ld, ld)
            kwargs["linear_output_norm"] = bool(d.get("use_output_norm", False))
            kwargs["linear_output_gate"] = bool(d.get("use_output_gate", False))
            if (lh, lh, ld, ld) == (
                nq, int(d.get("num_key_value_heads", nq)), hd, hd
            ) and not all(linear) and not kwargs["linear_output_norm"]:
                raise NotImplementedError(
                    "minicpm_sala: the two layer kinds have one shape and no "
                    "output norm: a layer's weights cannot say its kind"
                )
        rope = tuple(
            bool(d.get("lightning_use_rope", True) if lin else d.get("attn_use_rope", True))
            for lin in linear
        )
        if not all(rope):
            kwargs["layer_rope"] = rope
        sparse = d.get("sparse_config") or {}
        kwargs["sparse_attn_from"] = int(sparse.get("dense_len", 8192))
        kwargs["embed_multiplier"] = float(d.get("scale_emb", 1.0))
        kwargs["residual_multiplier"] = float(d.get("scale_depth", 1.0)) / n**0.5
        kwargs["logit_divisor"] = float(d.get("hidden_size", 4096)) / float(
            d.get("dim_model_base", d.get("hidden_size", 4096))
        )

    @staticmethod
    def _apply_ouro(kwargs: dict[str, Any], d: dict[str, Any]) -> None:
        """Ouro (``ouro``, a looped language model): one stack of plain
        multi-head softmax layers visited ``total_ut_steps`` times, each
        layer with FOUR norms (the sandwich residual on both sublayers:
        ``ffw_sandwich_norms`` with a plain ``x * scale`` RMSNorm), the
        final norm at every step's end and an exit gate beside it. No
        biases, no q/k norm, rotary over the whole head. The published
        configs carry ``use_sliding_window`` false; a window is refused,
        not guessed."""
        if d.get("use_sliding_window"):
            raise NotImplementedError(
                "ouro with use_sliding_window is not supported"
            )
        kwargs["sliding_window"] = None
        kwargs["ffw_sandwich_norms"] = True
        kwargs["total_ut_steps"] = int(d.get("total_ut_steps", 4))
        kwargs["early_exit_threshold"] = float(d.get("early_exit_threshold", 1.0))

    @staticmethod
    def _apply_glm5_next(kwargs: dict[str, Any], d: dict[str, Any]) -> None:
        """GLM-5.3 (``glm5_next_text``): ``layer_types`` lists each layer's
        mixer, ``linear_attention`` (KDA: ``linear_attn_config``) or
        ``deepseek_sparse_attention`` (latent attention with NO rotary part,
        ``qk_rope_head_dim`` 0, LoRA'd queries; dense over every earlier key
        while the prompt has at most ``index_topk`` tokens, refused past
        that: the indexer that selects keys there is not built);
        ``mlp_layer_types`` / ``first_k_dense_replace`` the dense and the
        expert layers (the DeepSeek router, one shared expert); ``hc_*`` the
        four-stream residual; ``swiglu_limit`` the clamp. Width convention
        as deepseek_v3; the held share of the experts from ``ep_size`` /
        ``ep_rank`` as mimo_v2_flash."""
        n = int(d.get("num_hidden_layers", 32))
        kinds = list(d.get("layer_types") or ["deepseek_sparse_attention"] * n)[:n]
        unknown = sorted(set(kinds) - {"linear_attention", "deepseek_sparse_attention"})
        if len(kinds) != n or unknown:
            raise ValueError(
                f"glm5_next_text layer_types has {len(kinds)} entries for {n} "
                f"layers (unknown kinds: {unknown})"
            )
        if not d.get("mla_use_nope", True) or int(d.get("qk_rope_head_dim") or 0):
            raise NotImplementedError(
                "glm5_next_text with a rotary part in its latent attention "
                "(mla_use_nope false / qk_rope_head_dim > 0) is not supported"
            )
        kwargs["kv_lora_rank"] = int(d.get("kv_lora_rank", 512))
        qlr = d.get("q_lora_rank")
        kwargs["q_lora_rank"] = int(qlr) if qlr else None
        kwargs["qk_nope_head_dim"] = int(d.get("qk_nope_head_dim", 256))
        kwargs["qk_rope_head_dim"] = 0
        kwargs["v_head_dim"] = int(d.get("v_head_dim", 256))
        kwargs["explicit_head_dim"] = None
        kwargs["query_pre_attn_scalar"] = float(kwargs["qk_nope_head_dim"])
        kwargs["sliding_window"] = None
        if d.get("attention_bias"):
            raise NotImplementedError("glm5_next_text with attention_bias")
        linear = tuple(k == "linear_attention" for k in kinds)
        if any(linear):
            la = d.get("linear_attn_config") or {}
            lh, ld = int(la.get("num_heads", 64)), int(la.get("head_dim", 128))
            kwargs["layer_linear"] = linear
            kwargs["linear_attn_shape"] = (lh, lh, ld, ld)
            kwargs["linear_kind"] = "kda"
            kwargs["linear_conv_size"] = int(la.get("short_conv_kernel_size", 4))
            kwargs["linear_gate_lower_bound"] = float(la.get("gate_lower_bound", -5))
            kwargs["linear_output_norm"] = kwargs["linear_output_gate"] = True
            if not -88.0 / 16 < kwargs["linear_gate_lower_bound"] < 0:
                raise NotImplementedError(
                    "glm5_next_text gate_lower_bound "
                    f"{kwargs['linear_gate_lower_bound']}: the chunked form "
                    "holds exp(+-cumsum g) over 16 rows in float32 only for "
                    "a bound in (-5.5, 0)"
                )
        # Past index_topk tokens the indexer picks a query's keys; up to
        # there top-k of fewer keys is all of them.
        kwargs["sparse_attn_from"] = int(d.get("index_topk", 2048)) + 1
        if d.get("mhc"):
            kwargs["hc_mult"] = int(d.get("hc_mult", 4))
            kwargs["hc_sinkhorn_iters"] = int(d.get("hc_sinkhorn_iters", 20))
            kwargs["hc_eps"] = float(d.get("hc_eps", 1e-6))
        lim = d.get("swiglu_limit")
        kwargs["swiglu_limit"] = None if lim is None else float(lim)
        n_routed = int(d.get("n_routed_experts") or 0)
        kwargs["num_local_experts"] = n_routed
        if not n_routed:
            return
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"glm5_next_text scoring_func {d.get('scoring_func')!r} (sigmoid is supported)"
            )
        kwargs["intermediate_size_mlp"] = int(d.get("intermediate_size", 12288))
        kwargs["intermediate_size"] = int(d.get("moe_intermediate_size", 2048))
        kwargs["num_experts_per_tok"] = int(d.get("num_experts_per_tok", 8))
        kwargs["moe_norm_topk_prob"] = bool(d.get("norm_topk_prob", True))
        kwargs["moe_n_group"] = int(d.get("n_group") or 1)
        kwargs["moe_topk_group"] = int(d.get("topk_group") or 1)
        rsf = d.get("routed_scaling_factor")
        kwargs["moe_routed_scaling_factor"] = 1.0 if rsf is None else float(rsf)
        nse = d.get("n_shared_experts")
        kwargs["n_shared_experts"] = 1 if nse is None else int(nse)
        mlps = d.get("mlp_layer_types")
        if mlps:
            moe = tuple(t == "sparse" for t in list(mlps)[:n])
        else:
            first = int(d.get("first_k_dense_replace", 0))
            moe = tuple(i >= first for i in range(n))
        if len(moe) != n:
            raise ValueError(
                f"glm5_next_text mlp_layer_types has {len(moe)} entries for {n} layers"
            )
        if not all(moe):
            kwargs["moe_layer_pattern"] = moe
        ep, rank = int(d.get("ep_size") or 1), int(d.get("ep_rank") or 0)
        if n_routed % ep or not 0 <= rank < ep:
            raise ValueError(
                f"glm5_next_text: {n_routed} experts do not split over ep_size {ep} "
                f"(ep_rank {rank})"
            )
        kwargs["moe_ep_size"], kwargs["moe_ep_rank"] = ep, rank

    @classmethod
    def from_hf_config(cls, d: dict[str, Any]) -> "LlamaConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        model_type = d.get("model_type", "llama")
        # Configs this framework saved itself (save_params marks them) carry
        # every native field explicitly and round-trip by field name. A
        # FOREIGN config.json only contributes fields that mean the same
        # thing for its declared model_type: a stray numerics-changing key
        # in a merged/"llamafied" export (qk_norm, attention_chunk_size,
        # layer_sliding, softcaps, ...) must be ignored, not silently
        # honoured — the family branches below re-derive those from the HF
        # names instead.
        # Migration: configs saved by earlier framework versions predate the
        # marker but always wrote native-only field names (attention_in_bias
        # is unconditional in save_params) — no foreign HF export carries it.
        native = bool(d.get("fls_native")) or "attention_in_bias" in d
        if native:
            kwargs = {k: v for k, v in d.items() if k in known}
        else:
            allowed = _UNIVERSAL_HF_FIELDS | _FAMILY_HF_FIELDS.get(
                model_type, frozenset()
            )
            kwargs = {k: v for k, v in d.items() if k in known and k in allowed}
        # Family-specific conventions (numerics-changing features either map
        # to a native field here or fail loudly — never silently drop).
        if model_type in ("llama", ""):
            if d.get("attention_bias"):  # HF Llama: one flag, all four projs
                kwargs.setdefault("attention_in_bias", True)
                kwargs.setdefault("attention_out_bias", True)
            # HF LlamaModel ignores a stray sliding_window key (common in
            # llamafied/merged exports); honouring it here would silently
            # change logits vs HF.
            kwargs["sliding_window"] = None
        elif model_type == "qwen2":
            # HF Qwen2 hard-codes bias=True on q/k/v, False on o_proj.
            kwargs.setdefault("attention_in_bias", True)
            kwargs.setdefault("attention_out_bias", False)
            cls._apply_qwen_window(kwargs, d)
        elif model_type in ("qwen3", "qwen3_moe"):
            # One attention_bias flag for all four projections (like Llama,
            # default False) + per-head-dim q/k RMSNorm.
            if d.get("attention_bias"):
                kwargs.setdefault("attention_in_bias", True)
                kwargs.setdefault("attention_out_bias", True)
            kwargs.setdefault("qk_norm", True)
            cls._apply_qwen_window(kwargs, d)
            if model_type == "qwen3":
                # Dense Qwen3Config's class default; Qwen3MoeConfig has NO
                # head_dim attribute (falls back to hidden/heads), so the
                # MoE branch must not invent one.
                kwargs.setdefault("explicit_head_dim", 128)
            if model_type == "qwen3_moe":
                if not d.get("num_experts") and not d.get("num_local_experts"):
                    raise ValueError("qwen3_moe config without num_experts")
                kwargs.setdefault("num_local_experts", d.get("num_experts", 0))
                kwargs.setdefault("num_experts_per_tok", d.get("num_experts_per_tok", 8))
                kwargs.setdefault("moe_norm_topk_prob", d.get("norm_topk_prob", False))
                # Dense layers (mlp_only_layers / decoder_sparse_step) are a
                # checkpoint-structure fact; record the pattern as metadata.
                step = d.get("decoder_sparse_step", 1)
                only = set(d.get("mlp_only_layers") or [])
                n = d.get("num_hidden_layers", 32)  # match the dataclass default
                pattern = tuple(
                    i not in only and (i + 1) % step == 0 for i in range(n)
                )
                if not all(pattern):
                    kwargs.setdefault("moe_layer_pattern", pattern)
        elif model_type == "gemma":
            kwargs.setdefault("norm_unit_offset", True)
            kwargs.setdefault("embed_scale", True)
            # GemmaConfig's class defaults (tie=True, head_dim=256) are
            # OMITTED from config.json by HF's to_diff_dict exactly when the
            # checkpoint uses them; our dataclass defaults differ, so apply
            # the family defaults here (explicit values still win).
            kwargs.setdefault("tie_word_embeddings", True)
            kwargs.setdefault("explicit_head_dim", 256)
            # HF GemmaMLP IGNORES the legacy hidden_act key entirely: when
            # hidden_activation is None it forces gelu_pytorch_tanh (the
            # original google/gemma config.json ships hidden_act='gelu' and
            # HF still runs the tanh approximation). Only a native config's
            # explicit hidden_act wins.
            if not native:
                kwargs["hidden_act"] = (
                    d.get("hidden_activation") or "gelu_pytorch_tanh"
                )
            kwargs["sliding_window"] = None
        elif model_type == "gemma2":
            kwargs.setdefault("norm_unit_offset", True)
            kwargs.setdefault("embed_scale", True)
            kwargs.setdefault("tie_word_embeddings", True)
            kwargs.setdefault("explicit_head_dim", 256)  # Gemma2Config default
            if not native:  # HF Gemma*MLP ignores the legacy hidden_act key
                kwargs["hidden_act"] = (
                    d.get("hidden_activation") or "gelu_pytorch_tanh"
                )
            kwargs["ffw_sandwich_norms"] = True
            # setdefault: explicit NATIVE keys (our own saved configs,
            # including explicit nulls) win over the HF names/defaults.
            kwargs.setdefault("attn_logit_softcap", d.get("attn_logit_softcapping", 50.0))
            kwargs.setdefault("final_logit_softcap", d.get("final_logit_softcapping", 30.0))
            kwargs.setdefault("query_pre_attn_scalar", 256)
            # Alternating local/global attention (HF default: every even
            # layer slides).
            cls._apply_sliding_pattern(
                kwargs, d, "gemma2", lambda i, n: (i + 1) % 2, 4096
            )
        elif model_type == "gemma3_text":
            kwargs.setdefault("norm_unit_offset", True)
            kwargs.setdefault("embed_scale", True)
            kwargs.setdefault("tie_word_embeddings", True)
            kwargs.setdefault("explicit_head_dim", 256)
            kwargs.setdefault("qk_norm", True)  # Gemma3RMSNorm, (1+w) style
            if not native:  # HF Gemma*MLP ignores the legacy hidden_act key
                kwargs["hidden_act"] = (
                    d.get("hidden_activation") or "gelu_pytorch_tanh"
                )
            kwargs["ffw_sandwich_norms"] = True
            kwargs.setdefault("query_pre_attn_scalar", d.get("query_pre_attn_scalar", 256))
            kwargs.setdefault("rope_theta", 1_000_000.0)  # global layers
            kwargs.setdefault("rope_local_theta", d.get("rope_local_base_freq", 10_000.0))
            # 5:1 local/global: every 6th layer is full attention.
            cls._apply_sliding_pattern(
                kwargs, d, "gemma3", lambda i, n: (i + 1) % 6 != 0, 4096
            )
        elif model_type == "gemma3":
            # Multimodal wrapper config: the language model is the nested
            # text_config (the splitter extracts its weights the same way).
            return cls.from_hf_config(extract_text_config(d))
        elif model_type == "llama4_text":
            kwargs.setdefault("explicit_head_dim", 128)  # Llama4 class default
            kwargs.setdefault("rope_interleaved", True)
            if d.get("use_qk_norm", True):
                kwargs.setdefault("qk_l2_norm", True)
            kwargs.setdefault("attn_temperature_tuning", d.get("attn_temperature_tuning", True))
            kwargs.setdefault("attn_floor_scale", float(d.get("floor_scale", 8192)))
            kwargs.setdefault("attn_scale_coef", float(d.get("attn_scale", 0.1)))
            n = d.get("num_hidden_layers", 48)
            # Chunked local layers (3:1 with NoPE full layers by default).
            if "layer_sliding" not in kwargs:
                chunked = cls._sliding_pattern(
                    d, "llama4",
                    lambda i, nn: (i + 1) % 4 != 0,
                    token="chunked_attention",
                )
                kwargs.setdefault(
                    "attention_chunk_size", d.get("attention_chunk_size", 8192)
                )
                if not any(chunked):
                    kwargs["attention_chunk_size"] = None
                elif not all(chunked):
                    kwargs["layer_sliding"] = chunked
            # NoPE layers: no_rope_layers[i] == 0.
            nr = d.get("no_rope_layers") or [
                0 if (i + 1) % 4 == 0 else 1 for i in range(n)
            ]
            if len(nr) != n:
                raise ValueError(
                    f"llama4 no_rope_layers has {len(nr)} entries for {n} layers"
                )
            if not all(nr):
                kwargs.setdefault("layer_rope", tuple(bool(x) for x in nr))
            # MoE interleave: moe_layers when present, else every
            # interleave_moe_layer_step-th layer.
            step = d.get("interleave_moe_layer_step", 1)
            moe_layers = d.get("moe_layers")
            if moe_layers is None:
                moe_layers = [i for i in range(n) if (i + 1) % step == 0]
            if d.get("num_local_experts", 16) and moe_layers:
                kwargs.setdefault("num_local_experts", d.get("num_local_experts", 16))
                kwargs.setdefault("num_experts_per_tok", d.get("num_experts_per_tok", 1))
                if len(moe_layers) != n:
                    kwargs.setdefault(
                        "moe_layer_pattern",
                        tuple(i in set(moe_layers) for i in range(n)),
                    )
            else:
                kwargs["num_local_experts"] = 0
            kwargs.setdefault("intermediate_size_mlp", d.get("intermediate_size_mlp"))
        elif model_type == "llama4":
            return cls.from_hf_config(extract_text_config(d))
        elif model_type == "deepseek_v3":
            if not native:
                # Multi-head latent attention + DeepSeek MoE. Width convention
                # follows the llama4 branch so ONE rule serves both mixed
                # dense/MoE families: intermediate_size = the EXPERT width
                # (HF moe_intermediate_size), intermediate_size_mlp = the dense
                # layers' width (HF intermediate_size). Configs this framework
                # saved itself skip the derivation entirely — their native
                # field names round-tripped above, and re-deriving from HF
                # names would corrupt them (the width swap in particular).
                kwargs["kv_lora_rank"] = int(d.get("kv_lora_rank", 512))
                qlr = d.get("q_lora_rank")
                kwargs["q_lora_rank"] = int(qlr) if qlr else None
                kwargs["qk_nope_head_dim"] = int(d.get("qk_nope_head_dim", 128))
                kwargs["qk_rope_head_dim"] = int(d.get("qk_rope_head_dim", 64))
                kwargs["v_head_dim"] = int(d.get("v_head_dim", 128))
                # HF's head_dim here is the ROTARY dim (= qk_rope_head_dim),
                # not a projection width — the MLA head_dim property derives
                # qk_nope + qk_rope instead.
                kwargs["explicit_head_dim"] = None
                kwargs["rope_interleaved"] = bool(d.get("rope_interleave", True))
                if d.get("attention_bias"):
                    # HF: bias on q_a/q_proj, kv_a_proj_with_mqa, o_proj.
                    kwargs.setdefault("attention_in_bias", True)
                    kwargs.setdefault("attention_out_bias", True)
                n_routed = int(d.get("n_routed_experts") or 0)
                kwargs["num_local_experts"] = n_routed
                if n_routed:
                    kwargs["intermediate_size_mlp"] = int(
                        d.get("intermediate_size", 11008)
                    )
                    kwargs["intermediate_size"] = int(
                        d.get("moe_intermediate_size", 2048)
                    )
                    kwargs["num_experts_per_tok"] = int(
                        d.get("num_experts_per_tok", 8)
                    )
                    kwargs["moe_norm_topk_prob"] = bool(d.get("norm_topk_prob", True))
                    kwargs["moe_n_group"] = int(d.get("n_group", 1))
                    kwargs["moe_topk_group"] = int(d.get("topk_group", 1))
                    kwargs["moe_routed_scaling_factor"] = float(
                        d.get("routed_scaling_factor", 1.0)
                    )
                    nse = d.get("n_shared_experts")
                    # Preserve an explicit 0 (shared-expert-ablated
                    # checkpoint); only absent/None defaults to 1.
                    kwargs["n_shared_experts"] = (
                        1 if nse is None else int(nse)
                    )
                    first_dense = int(d.get("first_k_dense_replace", 0))
                    n = d.get("num_hidden_layers", 32)
                    pattern = tuple(i >= first_dense for i in range(n))
                    if not all(pattern):
                        kwargs["moe_layer_pattern"] = pattern
                # Attention scale: qk_head_dim^-0.5 x mscale(factor,
                # mscale_all_dim)^2 under yarn (DeepseekV3Attention.__init__);
                # expressed through query_pre_attn_scalar (scale = qps^-0.5).
                qk_hd = kwargs["qk_nope_head_dim"] + kwargs["qk_rope_head_dim"]
                rs_d = d.get("rope_scaling") or {}
                mad = rs_d.get("mscale_all_dim")
                if mad and float(rs_d.get("factor", 1.0)) > 1.0:
                    import math

                    m = 0.1 * float(mad) * math.log(float(rs_d["factor"])) + 1.0
                    kwargs["query_pre_attn_scalar"] = qk_hd / m**4
                else:
                    kwargs["query_pre_attn_scalar"] = float(qk_hd)
        elif model_type == "mimo_v2_flash":
            if not native:
                cls._apply_mimo_v2(kwargs, d)
        elif model_type == "minicpm_sala":
            if not native:
                cls._apply_minicpm_sala(kwargs, d)
        elif model_type == "ouro":
            if not native:
                cls._apply_ouro(kwargs, d)
        elif model_type == "glm5_next_text":
            if not native:
                cls._apply_glm5_next(kwargs, d)
        elif model_type in ("mistral", "mixtral", "phi3"):
            # sliding_window flows through by field name (may be null);
            # mixtral's num_local_experts/num_experts_per_tok likewise.
            # phi3's fused qkv/gate_up projections are a CHECKPOINT layout
            # (split at conversion, utils/checkpoint.py), not a model delta;
            # its longrope scaling parses via the generic rope branch below.
            if model_type == "mixtral" and not d.get("num_local_experts"):
                raise ValueError("mixtral config without num_local_experts")
        else:
            raise NotImplementedError(
                f"model_type {model_type!r} is not supported "
                "(llama, mistral, phi3, qwen2, qwen3, qwen3_moe, mixtral, gemma, "
                "gemma2, gemma3_text, llama4_text, deepseek_v3, mimo_v2_flash, "
                "minicpm_sala, ouro, glm5_next_text are)"
            )
        if model_type not in (
            "mixtral", "llama4_text", "qwen3_moe", "deepseek_v3", "mimo_v2_flash",
            "glm5_next_text",
        ):
            # A stray num_local_experts key in a dense export must not flip
            # the model into MoE mode (same stray-key defence as
            # sliding_window above).
            kwargs["num_local_experts"] = 0
        if d.get("head_dim") and model_type not in ("deepseek_v3", "glm5_next_text"):
            # deepseek's top-level head_dim is the ROTARY dim, not a
            # projection width; the MLA head_dim property derives
            # qk_nope + qk_rope itself.
            kwargs["explicit_head_dim"] = d["head_dim"]
        kwargs.setdefault("num_key_value_heads", d.get("num_attention_heads", 32))
        for key in (
            "layer_sliding",
            "layer_rope",
            "moe_layer_pattern",
            "rope_long_factor",
            "rope_short_factor",
            "local_attn_shape",
            "layer_linear",
            "linear_attn_shape",
        ):
            if kwargs.get(key) is not None:
                # json round-trips tuples as lists; fields must stay hashable.
                kwargs[key] = tuple(kwargs[key])
        if kwargs.get("sliding_window") and kwargs.get("attention_chunk_size"):
            raise ValueError(
                "sliding_window and attention_chunk_size are mutually exclusive"
            )
        act = kwargs.get("hidden_act", "silu")
        if act not in SUPPORTED_ACTIVATIONS:
            # Must fail here, not as a KeyError deep inside a jitted forward.
            raise NotImplementedError(
                f"hidden_act {act!r} is not supported "
                f"(one of {sorted(SUPPORTED_ACTIVATIONS)})"
            )
        rs = d.get("rope_scaling") or {}
        if rs:
            kind = rs.get("rope_type", rs.get("type"))
            if kind not in ("linear", "llama3", "yarn", "longrope"):
                raise NotImplementedError(
                    f"rope_scaling type {kind!r} is not supported yet"
                )
            factor = float(rs.get("factor", 1.0))
            kwargs["rope_scaling_kind"] = kind
            kwargs["rope_scaling_factor"] = factor
            if kind == "llama3":
                kwargs["rope_low_freq_factor"] = float(rs.get("low_freq_factor", 1.0))
                kwargs["rope_high_freq_factor"] = float(rs.get("high_freq_factor", 4.0))
                kwargs["rope_original_max_position"] = int(
                    rs.get("original_max_position_embeddings", 8192)
                )
            elif kind == "yarn":
                import math

                kwargs["rope_beta_fast"] = float(rs.get("beta_fast") or 32)
                kwargs["rope_beta_slow"] = float(rs.get("beta_slow") or 1)
                kwargs["rope_truncate"] = bool(rs.get("truncate", True))
                kwargs["rope_original_max_position"] = int(
                    rs.get("original_max_position_embeddings")
                    or d.get("max_position_embeddings", 2048)
                )
                # HF _compute_yarn_parameters: attention_factor wins; else
                # derived from factor (and DeepSeek's mscale pair).
                af = rs.get("attention_factor")
                if af is None:
                    def get_mscale(scale, m=1.0):
                        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

                    ms, mad = rs.get("mscale"), rs.get("mscale_all_dim")
                    af = (
                        get_mscale(factor, ms) / get_mscale(factor, mad)
                        if ms and mad
                        else get_mscale(factor)
                    )
                kwargs["rope_attention_factor"] = float(af)
            elif kind == "longrope":
                import math

                # transformers _compute_longrope_parameters: Phi-3 carries
                # original_max_position_embeddings at the config top level;
                # when present, the effective factor is the max/original
                # ratio (overriding any rope_scaling "factor" key). The
                # attention factor (applied to cos/sin in both regimes)
                # is sqrt(1 + ln(factor)/ln(original_max)) unless the
                # config names one explicitly.
                lf, sf = rs.get("long_factor"), rs.get("short_factor")
                if not lf or not sf:
                    raise ValueError(
                        "longrope rope_scaling needs long_factor and "
                        "short_factor lists"
                    )
                kwargs["rope_long_factor"] = tuple(float(x) for x in lf)
                kwargs["rope_short_factor"] = tuple(float(x) for x in sf)
                max_pos = int(d.get("max_position_embeddings", 2048))
                orig = d.get("original_max_position_embeddings") or rs.get(
                    "original_max_position_embeddings"
                )
                if orig:
                    factor = max_pos / int(orig)
                else:
                    orig = max_pos
                kwargs["rope_original_max_position"] = int(orig)
                af = rs.get("attention_factor")
                if af is None:
                    af = (
                        1.0
                        if factor <= 1.0
                        else math.sqrt(1 + math.log(factor) / math.log(int(orig)))
                    )
                kwargs["rope_attention_factor"] = float(af)
                kwargs["rope_scaling_factor"] = float(factor)
        cfg = cls(**kwargs)
        if cfg.rope_scaling_kind == "longrope":
            for nm, fac in (
                ("long_factor", cfg.rope_long_factor),
                ("short_factor", cfg.rope_short_factor),
            ):
                if fac is None or len(fac) != cfg.head_dim // 2:
                    raise ValueError(
                        f"longrope {nm} needs {cfg.head_dim // 2} entries "
                        f"(head_dim {cfg.head_dim}), got "
                        f"{None if fac is None else len(fac)}"
                    )
        return cfg

    @classmethod
    def from_pretrained(cls, model_path: str) -> "LlamaConfig":
        with open(os.path.join(model_path, "config.json")) as f:
            return cls.from_hf_config(json.load(f))


@dataclasses.dataclass(frozen=True)
class FrameworkConfig:
    """Runtime flags — the same surface as the reference CLI
    (``/root/reference/main.py:30-49``) plus TPU-specific knobs.

    ``storage_location`` gains a ``tpu`` value (activations stay in HBM); the
    reference's ``gpu`` is accepted as an alias. Unset (``None``, the
    default) the single-executor scoring pass keeps each block's activations
    on the chip while they fit a budget it derives from the chip's memory
    and the residency tier's plan, and sends the rest the ``cpu`` way
    (``StreamingExecutor._run_pass``); every other path (the MP pipeline,
    KV decode, serving, training) reads unset as ``cpu``. Unlike the reference's
    ``--data_parallel`` bool footgun (any non-empty string parsed as True,
    ``/root/reference/main.py:40``), this is a real bool everywhere.
    """

    model_path: str = "./"
    num_batch: int = 1
    layer_num_per_shard: int = 1
    # 'tpu' | 'cpu' | 'disk' ('gpu' alias of 'tpu'); None = not set (see above)
    storage_location: str | None = None
    max_activation_in_cpu: int = 100
    data_parallel: bool = False
    disk_folder: str = "./temp"
    num_gen_token: int = 1
    # --- TPU-specific knobs (not in the reference) ---
    max_token_len: int = DEFAULT_MAX_TOKEN_LEN
    dtype: str = "bfloat16"  # compute/storage dtype on device ('float16'|'bfloat16'|'float32')
    block_size: int = 8  # prompts batched together per jitted layer call
    # Shards prefetched ahead of compute (0 = synchronous, the reference's
    # serialized schedule). None = auto: 2 on an accelerator backend (overlap
    # the host->HBM upload of shard t+1 with shard t's compute), 0 on the CPU
    # backend — there "device" memory IS host memory, so there is no transfer
    # link to overlap and the producer thread only steals cores/GIL from
    # XLA:CPU's own compute.
    prefetch_depth: int | None = None
    num_devices: int = 0  # 0 = all visible devices
    bucket_multiple: int = 64  # sequence lengths padded up to a multiple of this
    # Pallas flash-attention kernels. None = auto: enabled on TPU (their
    # speed against the XLA attention on the chip: not measured, PERF.md);
    # shapes the kernel can't tile fall back per-call
    # (models/llama.py checks pallas_attention.supports() at trace time).
    use_pallas: bool | None = None
    # Tensor parallelism for the streaming scorer: shard every streamed
    # layer's matmuls Megatron-style over this many chips (per-chip weight
    # HBM drops by the factor; XLA emits the ICI all-reduces). 1 = off.
    # Composes with data_parallel (dp groups of tp chips); supersedes the MP
    # pipeline when set.
    tensor_parallel: int = 1
    verbose_metrics: bool = False  # one JSON line per structured event (stderr)
    profile_dir: str = ""  # jax.profiler trace output dir ("" = off)
    # Sweep-timeline span tracing (obs/trace.py): record shard loads,
    # device puts, compute, source waits, cache hits, pin loads, retry/
    # heal events, and (serving) the wave lifecycle into a bounded ring,
    # correlated by sweep_id/shard_idx/wave_id/request_id. Zero-cost
    # no-op when False. The CLIs export at run end to ``trace_out``
    # (Chrome trace-event JSON — Perfetto-loadable — or JSONL when the
    # path ends in .jsonl); ``cli trace-report`` analyzes the file.
    trace: bool = False
    trace_out: str = ""  # "" = default fls_trace.json when trace is on
    # Black-box flight recorder (obs/events.py + obs/incident.py;
    # docs/incidents.md). journal_dir enables the durable append-only
    # JSONL event journal every failure-path site writes through
    # (engine recoveries, wave aborts, replica death/drain/redispatch,
    # quarantines, re-read heals, pressure steps, watchdog stalls,
    # preemptions, SLO budget exhaustion). "" = off (zero cost: one
    # bool check per failure event). The journal rotates atomically at
    # journal_max_mb (one previous generation kept) and a write failure
    # degrades to a counted drop, never an engine error.
    journal_dir: str = ""
    journal_max_mb: float = 16.0
    # incidents_dir arms the incident recorder: a journal event at (or
    # above) incident_trigger severity captures a self-contained bundle
    # directory — journal tail, full metrics snapshot, trace ring as
    # Chrome trace JSON, resolved config, manifest — debounced so a
    # failure storm yields ONE bundle (the capture settles
    # incident_settle_s after the trigger, extended while trigger-level
    # events keep landing, then debounces for incident_debounce_s).
    # The dir is disk-budgeted at incidents_max_mb, oldest evicted.
    # Setting incidents_dir without journal_dir keeps the journal
    # beside the bundles. "" = off.
    incidents_dir: str = ""
    incidents_max_mb: float = 256.0
    incident_trigger: str = "error"  # info|warning|error|critical
    incident_debounce_s: float = 60.0
    incident_settle_s: float = 1.0
    resume: bool = False  # disk mode: resume from the last completed shard
    # Long context: prompts whose PREFIX exceeds max_token_len are scored
    # exactly via sequence parallelism (ring attention over an 'sp' mesh of
    # the visible chips; cap becomes n_chips * max_token_len) instead of the
    # reference's silent truncation (/root/reference/utils.py:14,250,254).
    long_context: bool = False
    # Weights-resident KV decode: when the model's device-materialised
    # weights fit comfortably in HBM, keep every streamed shard on chip
    # after the prefill pass and run decode steps with ZERO weight
    # transfers (the reference re-streams the full model per token,
    # /root/reference/main.py:65-76; plain KV decode still re-streams the
    # weights each step). 'auto' = on iff total weight bytes (for the
    # compute dtype, split over the tp/mp chips) fit within 45% of the
    # chip's known HBM — leaving room for KV caches, activations, and the
    # prefill-time prefetch queue; unknown HBM resolves to off.
    decode_resident: str = "auto"  # 'auto' | 'on' | 'off'
    # Fused decode: run ALL greedy decode steps as one jitted scan per block
    # (runtime/decode._fused_decode_steps) instead of one dispatch per shard
    # per step. 'auto' fuses whenever the preconditions hold (weights
    # resident, greedy selection, one placement target); 'on' additionally
    # raises if they don't (so a user asking for it learns why not); 'off'
    # keeps the per-step loop (bitwise-stable vs the streamed path — fusing
    # changes XLA fusion boundaries, so float results can differ in the
    # last ulp).
    decode_fused: str = "auto"  # 'auto' | 'on' | 'off'
    # Speculative decode (kv_cache mode): each streamed pass verifies
    # `speculative_k` prompt-lookup-drafted tokens PLUS the next token in
    # one K+1-position decode step, emitting 1..K+1 tokens per pass —
    # dividing the number of full weight streams per generated token by the
    # acceptance factor. Greedy-exact (verification accepts precisely the
    # tokens sequential greedy would emit); 0 disables. Ignored when the
    # fused resident path engages (resident steps don't re-stream weights,
    # so there is nothing to amortise).
    speculative_k: int = 0
    # Sampling controls (generation_loop.sample_token semantics): 0 = greedy
    # argmax (exact reference behaviour, /root/reference/main.py:47-48 left
    # the temperature flag commented out). Deterministic given seed.
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    # Transient-I/O retry knobs (faults/retry.py RetryPolicy; the weight
    # stream's disk reads and host->device puts retry under this before a
    # typed ShardLoadError surfaces). attempts=1 disables retrying.
    io_retry_attempts: int = 4
    io_retry_base_s: float = 0.05  # first backoff; doubles per attempt
    io_retry_deadline_s: float = 60.0  # overall wall cap per call; 0 = none
    # Weight-stream integrity verification (integrity/manifest.py): every
    # layer load checksums its tensors against the model dir's
    # integrity.json; a mismatch retries (re-read heals page-cache/NFS
    # corruption) and only persistent corruption raises a typed
    # ShardCorruptError. The crc pass is amortized: a file generation is
    # hashed once and later sweeps reuse the cached clean verdict (any
    # on-disk change re-verifies), so steady-state sweeps pay no per-byte
    # hash cost. Dirs with no manifest load unverified with a one-time
    # warning.
    verify_weights: bool = True
    # Host-resident shard cache (runtime/hostcache.py): pins fully-built,
    # upload-ready host shard trees so steady-state sweeps (the serving
    # engine's cycling source, multi-sweep offline decode) skip disk read
    # + parse + checksum entirely and go straight to device_put. None =
    # auto: a fraction of the host's available RAM, and OFF while fault
    # injection is enabled (chaos runs must exercise the per-load fault
    # sites every sweep). 0 disables; any other value is a budget in GB.
    # Entries are stat-guarded and invalidated on quarantine/manifest
    # change, so PR 4's corruption self-healing is unaffected.
    host_cache_gb: float | None = None
    # Paged prefix-KV pool (runtime/kvpool.py): process-lived, refcounted
    # pages share a recurring prefix's post-RoPE KV across admission waves
    # with copy-on-write at the first divergent token, so a hot system
    # prompt prefills once per PROCESS instead of once per wave.
    # kv_page_tokens: rows per page (the sharing granularity; <= 0
    # disables the pool). kv_pool_gb: host-RAM budget for resident pages —
    # None = auto (a small slice of available RAM; unlike the shard cache
    # it stays ON under fault injection, because the pool's spill reads
    # are themselves chaos sites), 0 disables. kv_host_spill: True spills
    # cold pages to checksummed disk files that heal on read (PR 4
    # machinery); False drops them (the prefix simply re-prefills later).
    kv_page_tokens: int = 16
    kv_pool_gb: float | None = None
    kv_host_spill: bool = True
    # Device residency tier (runtime/residency.py): HBM byte budget for
    # pinning the hottest layers (embedding, lm_head, final norm, then as
    # many transformer blocks as fit) permanently on chip — pinned layers
    # are subtracted from every sweep's weight stream, cutting the
    # host->HBM link traffic by exactly their bytes while outputs stay
    # token-identical. None (default) = auto: measured free HBM minus a
    # headroom, the larger of ACTIVATION_HEADROOM_FRACTION of the chip and
    # what the weight source itself holds in flight ((prefetch depth + 2)
    # x the largest shard) plus SCRATCH_HEADROOM_FRACTION; a model that
    # fits is resident whole, one that does not pins what the headroom
    # allows. Auto is OFF under fault injection (chaos schedules must keep
    # their per-load draws; an explicit budget still wins) and on chips
    # with unknown HBM (the CPU backend). 0 disables. A pin is the first
    # sweep's own bytes: the first source of a process streams every layer
    # through the manifest-verified path as ever and keeps what it placed
    # of the planned ones; pins survive serving source restarts and wave
    # recoveries; a planned layer whose load fails past its retries is
    # demoted back to streaming, so wrong bytes are never resident.
    hbm_pin_gb: float | None = None
    # Threads in the loader's page-cache readahead pool
    # (utils/native.py FilePrefetcher — posix_fadvise(WILLNEED) issuers,
    # ~zero CPU each; more threads help deep dirs on high-QD storage).
    readahead_threads: int = 2
    # Device-resident score cap (executor.ScoreSink): at most this many
    # head-stage score slices stay pending on device before older ones
    # resolve to host numpy. Larger values defer host syncs further on
    # big-batch runs at the cost of HBM for the pending slices.
    score_sink_max_device: int = 16
    # Deterministic fault injection (off by default; the --chaos CLI flag
    # and the chaos tests enable it). Frozen sub-config keeps this config
    # hashable.
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    # Resource-pressure brownout ladder (off by default; the --pressure
    # CLI flag enables it — runtime/pressure.py, docs/pressure.md).
    pressure: PressureConfig = dataclasses.field(default_factory=PressureConfig)
    # Multi-tenant LoRA adapter serving (off by default; --adapter_dir
    # enables it — adapters/, docs/adapters.md).
    adapters: AdapterConfig = dataclasses.field(default_factory=AdapterConfig)

    def __post_init__(self) -> None:
        loc = self.storage_location
        if loc == "gpu":
            object.__setattr__(self, "storage_location", "tpu")
        elif loc not in (None, "tpu", "cpu", "disk"):
            raise ValueError(f"storage_location must be tpu|cpu|disk, got {loc!r}")
        if self.layer_num_per_shard < 1:
            raise ValueError("layer_num_per_shard must be >= 1")
        if self.num_batch < 1:
            raise ValueError("num_batch must be >= 1")
        if self.num_gen_token < 1:
            # 0 would deadlock DP decode: the broadcast source is built with
            # rounds=num_gen_token (1 in resident mode), so its producer
            # would push nothing while every consumer blocks on an empty
            # queue.
            raise ValueError("num_gen_token must be >= 1")
        if self.tensor_parallel < 1:
            raise ValueError("tensor_parallel must be >= 1")
        if self.prefetch_depth is not None and self.prefetch_depth < 0:
            raise ValueError("prefetch_depth must be >= 0 (or None for auto)")
        # tensor_parallel + data_parallel COMPOSE: the visible chips
        # partition into dp groups of tp chips each; every group streams the
        # model Megatron-sharded over its own tp sub-mesh while the prompt
        # batch splits across groups (orchestration validates the chip
        # count at run time, when the device list is known).
        if (self.top_k or self.top_p) and self.temperature <= 0:
            # Silent no-op filters would masquerade as sampling.
            raise ValueError("top_k/top_p require temperature > 0")
        if self.decode_resident not in ("auto", "on", "off"):
            raise ValueError(
                "decode_resident must be auto|on|off, "
                f"got {self.decode_resident!r}"
            )
        if self.decode_fused not in ("auto", "on", "off"):
            raise ValueError(
                f"decode_fused must be auto|on|off, got {self.decode_fused!r}"
            )
        if not 0 <= self.speculative_k <= 64:
            raise ValueError(
                f"speculative_k must be in [0, 64], got {self.speculative_k}"
            )
        if self.speculative_k and self.temperature > 0:
            # Greedy verification is exact; sampled verification would need
            # rejection sampling to preserve the output distribution —
            # loudly unsupported rather than silently wrong.
            raise ValueError("speculative_k requires greedy (temperature=0)")
        if self.io_retry_attempts < 1:
            raise ValueError("io_retry_attempts must be >= 1")
        if self.io_retry_base_s < 0 or self.io_retry_deadline_s < 0:
            raise ValueError("io_retry_base_s/io_retry_deadline_s must be >= 0")
        if self.host_cache_gb is not None and self.host_cache_gb < 0:
            raise ValueError(
                "host_cache_gb must be >= 0 (or None for auto), got "
                f"{self.host_cache_gb}"
            )
        if self.kv_pool_gb is not None and self.kv_pool_gb < 0:
            raise ValueError(
                "kv_pool_gb must be >= 0 (or None for auto), got "
                f"{self.kv_pool_gb}"
            )
        if self.hbm_pin_gb is not None and self.hbm_pin_gb < 0:
            raise ValueError(
                "hbm_pin_gb must be >= 0 (or None for auto), got "
                f"{self.hbm_pin_gb}"
            )
        if self.readahead_threads < 1:
            raise ValueError("readahead_threads must be >= 1")
        if self.score_sink_max_device < 1:
            raise ValueError("score_sink_max_device must be >= 1")
        if self.journal_max_mb <= 0:
            raise ValueError("journal_max_mb must be > 0")
        if self.incidents_max_mb <= 0:
            raise ValueError("incidents_max_mb must be > 0")
        if self.incident_trigger not in ("info", "warning", "error", "critical"):
            raise ValueError(
                "incident_trigger must be info|warning|error|critical, "
                f"got {self.incident_trigger!r}"
            )
        if self.incident_debounce_s < 0 or self.incident_settle_s < 0:
            raise ValueError(
                "incident_debounce_s/incident_settle_s must be >= 0"
            )

    def effective_host_cache_bytes(self) -> int:
        """Resolve the tri-state ``host_cache_gb`` to a byte budget.

        Explicit value -> that many GB (0 = off). None (auto) -> a
        fraction of the host's currently-available RAM — except under
        fault injection, where auto resolves to OFF: the chaos sites fire
        inside the per-load read path, and a cache hit would silently
        skip the very draws a seeded chaos schedule exists to make (an
        EXPLICIT budget still wins for chaos cache-parity tests). Unknown
        free RAM (non-Linux) also resolves to off."""
        if self.host_cache_gb is not None:
            return int(self.host_cache_gb * 1e9)
        if self.faults.enabled:
            return 0
        from flexible_llm_sharding_tpu.runtime.hostcache import (
            auto_budget_bytes,
        )

        return auto_budget_bytes()

    def effective_kv_pool_bytes(self) -> int:
        """Resolve the tri-state ``kv_pool_gb`` to a byte budget.

        Explicit value -> that many GB (0 = off). None (auto) -> a small
        slice of the host's available RAM (kvpool._auto_budget_bytes).
        Unlike the shard cache, auto stays ON under fault injection: the
        pool's spill reads are themselves corrupt_activation chaos sites,
        so chaos runs keep (and exercise) their draws through the pool."""
        if self.kv_pool_gb is not None:
            return int(self.kv_pool_gb * 1e9)
        from flexible_llm_sharding_tpu.runtime.kvpool import (
            _auto_budget_bytes,
        )

        return _auto_budget_bytes()

    def effective_adapter_bytes(self) -> int:
        """Resolve the tri-state ``adapters.max_gb`` to a byte budget.

        Explicit value -> that many GB (0 = off). None (auto) -> a small
        slice of the host's available RAM (adapters.loader's auto
        budget). Like the KV pool — and unlike the shard cache — auto
        stays ON under fault injection: the adapter store's delta reads
        are themselves ``corrupt_shard`` chaos sites (the chaos smoke
        serves adapters *under* faults), so chaos runs must keep their
        draws rather than lose the store entirely."""
        if self.adapters.max_gb is not None:
            return int(self.adapters.max_gb * 1e9)
        from flexible_llm_sharding_tpu.adapters.loader import (
            _auto_budget_bytes,
        )

        return _auto_budget_bytes()

    def effective_hbm_pin_bytes(self, device=None, in_flight_bytes: int = 0) -> int:
        """Resolve the tri-state ``hbm_pin_gb`` to a pin-tier byte budget.

        Explicit value -> that many GB (0 = off). None (auto, the
        default) -> measured free HBM minus a headroom that covers
        ``in_flight_bytes``, what a weight source holds on the chip while
        it streams (residency.auto_pin_budget_bytes) — except under fault
        injection, where auto resolves to OFF: pinned layers skip the
        per-sweep load path, silently starving a seeded chaos schedule of
        its draws (an EXPLICIT budget still wins, for chaos pin-parity
        tests). Unknown HBM (the CPU backend, unrecognized chips) also
        resolves to off."""
        if self.hbm_pin_gb is not None:
            return int(self.hbm_pin_gb * 1e9)
        if self.faults.enabled:
            return 0
        from flexible_llm_sharding_tpu.runtime.residency import (
            auto_pin_budget_bytes,
        )

        return auto_pin_budget_bytes(device, in_flight_bytes)

    def retry_policy(self):
        """The transient-I/O RetryPolicy for this run's weight stream
        (imported lazily: faults/inject.py imports this module)."""
        from flexible_llm_sharding_tpu.faults.retry import RetryPolicy

        return RetryPolicy(
            max_attempts=self.io_retry_attempts,
            base_delay_s=self.io_retry_base_s,
            deadline_s=self.io_retry_deadline_s or None,
        )

    def effective_prefetch_depth(self) -> int:
        """Resolve the tri-state ``prefetch_depth``: explicit value, or auto —
        2 when the default backend is an accelerator (real host->HBM link to
        hide), 0 on CPU (the overlapped schedule degenerates: no link, and
        the producer thread contends with XLA:CPU compute for cores)."""
        if self.prefetch_depth is not None:
            return self.prefetch_depth
        import jax

        # A backend that fails to come up propagates: "no prefetch" must
        # never be the silent reading of a chip that did not start.
        return 2 if jax.devices()[0].platform != "cpu" else 0

    def decode_resident_enabled(
        self, model_cfg, n_weight_chips: int = 1, device=None
    ) -> bool:
        """Resolve the tri-state ``decode_resident`` for a model.

        ``n_weight_chips``: how many chips the streamed weights divide over
        (tensor_parallel width, or the MP pipeline's stage count) — residency
        is judged per chip. Auto requires a KNOWN HBM capacity; the CPU
        backend (tests) and unrecognised devices resolve to off, so the
        fast path is only ever taken where the budget is real.
        """
        if self.decode_resident == "on":
            return True
        if self.decode_resident == "off":
            return False
        from flexible_llm_sharding_tpu.utils.metrics import (
            chip_hbm_gb,
            weight_bytes_per_chip,
        )

        hbm_gb = chip_hbm_gb(device)  # None on the CPU; raises on an unknown TPU
        if not hbm_gb:
            return False
        per_chip = weight_bytes_per_chip(model_cfg, self.dtype, n_weight_chips)
        return per_chip <= 0.45 * hbm_gb * 1e9

    def pallas_enabled(self) -> bool:
        """Resolve the tri-state ``use_pallas``: explicit value, or auto —
        on iff the default backend's devices are real TPUs (elsewhere the
        kernels would run in interpret mode, which is only slower)."""
        if self.use_pallas is not None:
            return self.use_pallas
        import jax

        # No except: a backend error is the caller's error, not "no kernels".
        return jax.devices()[0].platform == "tpu"


def _parse_tenant_map(spec: str, what: str) -> dict[str, float]:
    """Parse a ``"tenantA=2,tenantB=0.5"`` CLI spec into ``{tenant: value}``.
    Shared by SchedConfig's weight and rate-limit fields so the two can't
    grow divergent syntaxes; raises ValueError naming the offending entry."""
    out: dict[str, float] = {}
    for entry in (e.strip() for e in spec.split(",") if e.strip()):
        name, sep, value = entry.partition("=")
        if not sep or not name:
            raise ValueError(
                f"{what}: bad entry {entry!r} (expected tenant=value)"
            )
        try:
            out[name] = float(value)
        except ValueError:
            raise ValueError(
                f"{what}: non-numeric value in {entry!r}"
            ) from None
    return out


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Multi-tenant sweep scheduler (serve/sched/; docs/scheduling.md).

    Off by default — the admission queue then pops strict FIFO, exactly
    the pre-scheduler serving path. Enabled (``--sched``), the queue pops
    by STRICT PRIORITY across SLO classes (interactive > standard >
    best_effort) with deficit-weighted round-robin across tenants inside
    a class, tenants can carry token-bucket rate limits (over-limit
    submits resolve as typed ``RateLimited`` rejections with a
    ``retry_after_s`` hint), an interactive request stuck behind
    best-effort waves preempts the youngest best-effort wave at a
    shard-0 sweep boundary (never mid-sweep; the preempted requests
    resume token-identically), and same-prefix requests coalesce into
    one shared-prefix prefill."""

    enabled: bool = False
    # Per-class default ADMISSION deadlines (seconds), applied when a
    # request names neither its own deadline nor one via the serve-level
    # default; 0 = no class default (fall back to
    # ServeConfig.default_deadline_s).
    interactive_deadline_s: float = 0.0
    standard_deadline_s: float = 0.0
    best_effort_deadline_s: float = 0.0
    # Deficit-round-robin weights: "tenantA=4,tenantB=1"; unlisted
    # tenants weigh 1. A tenant with weight w gets ~w shares of each
    # class's admission budget while it has queued work.
    tenant_weights: str = ""
    # Token-bucket rate limits in requests/second: "tenantA=5"; unlisted
    # tenants are unlimited. Over-limit submits resolve as typed
    # RateLimited (a QueueFull subclass) carrying retry_after_s.
    tenant_limits: str = ""
    # Bucket capacity (burst) in requests, shared by every limited
    # tenant: a tenant idle long enough accumulates up to this many
    # instantly-admittable requests.
    tenant_burst: float = 4.0
    # Sweep-boundary preemption: an interactive request waiting while
    # every active-request slot is held and a best-effort wave is in
    # flight retires the YOUNGEST best-effort wave at the next shard-0
    # boundary; its requests re-enqueue with generated-so-far tokens
    # folded into their suffixes and resume token-identically.
    preempt: bool = True
    # Admission-time prefix coalescing: same-tokenized-prefix requests
    # admitted at one boundary merge into one wave entry that prefills
    # the shared prefix KV once and fans the suffix/decode streams out
    # per request.
    coalesce: bool = True
    # Fleet routing (serve/router.py): multiply the router's phase
    # weight by this for interactive requests, so interactive work lands
    # on the replica nearest its next shard-0 admission point.
    interactive_phase_boost: float = 2.0

    def __post_init__(self) -> None:
        for name in ("interactive_deadline_s", "standard_deadline_s",
                     "best_effort_deadline_s"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0 (0 = no default)")
        weights = _parse_tenant_map(self.tenant_weights, "tenant_weights")
        for t, w in weights.items():
            # The DRR loop's visit bound is ~1/min_weight; a zero or
            # absurdly small weight would spin it, not starve gracefully.
            if not 0.01 <= w <= 1e6:
                raise ValueError(
                    f"tenant_weights: weight for {t!r} must be in "
                    f"[0.01, 1e6], got {w}"
                )
        limits = _parse_tenant_map(self.tenant_limits, "tenant_limits")
        for t, r in limits.items():
            if r <= 0:
                raise ValueError(
                    f"tenant_limits: rate for {t!r} must be > 0 "
                    "(omit the tenant for unlimited)"
                )
        if self.tenant_burst < 1:
            raise ValueError("tenant_burst must be >= 1")
        if self.interactive_phase_boost < 1:
            raise ValueError(
                "interactive_phase_boost must be >= 1 (1 = no boost)"
            )

    def tenant_weight_map(self) -> dict[str, float]:
        return _parse_tenant_map(self.tenant_weights, "tenant_weights")

    def tenant_limit_map(self) -> dict[str, float]:
        return _parse_tenant_map(self.tenant_limits, "tenant_limits")


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """SLO targets + error budgets (obs/slo.py; docs/incidents.md has
    the budget math). Off by default — the per-class latency exports
    then carry no contract, exactly the pre-SLO behaviour.

    Enabled, the tracker turns the existing ``ttft_by_class`` /
    ``latency_by_class`` streams into error-budget accounting: a p95
    target allows 5% of samples over the line, the burn rate is the
    violating fraction over that allowance, and a class that exhausts
    its budget (burn rate >= 1 with at least ``min_samples`` samples)
    emits an ``slo_budget_exhausted`` journal event — which, with the
    incident recorder armed, captures a bundle exactly like a crash."""

    enabled: bool = False
    # Per-class p95 TTFT targets in seconds, the tenant-map syntax:
    # "interactive=0.5,standard=2.0" (unlisted classes carry no target).
    ttft_p95_s: str = ""
    # Aggregate per-token decode-latency p95 target in seconds (0 = off).
    token_latency_p95_s: float = 0.0
    # Availability target as a fraction of requests that must complete
    # (e.g. 0.999); failed requests burn the 1-target budget. 0 = off.
    availability_target: float = 0.0
    # Budgets are not judged (no exhaustion events) below this many
    # samples — a single slow first request must not trip a page.
    min_samples: int = 20

    def __post_init__(self) -> None:
        targets = _parse_tenant_map(self.ttft_p95_s, "ttft_p95_s")
        if targets:
            # Lazy import: utils.metrics mirrors the sched class names
            # (importing serve here would cycle); config stays light.
            from flexible_llm_sharding_tpu.utils.metrics import (
                SLO_CLASS_NAMES,
            )
        for cls, target in targets.items():
            if cls not in SLO_CLASS_NAMES:
                raise ValueError(
                    f"ttft_p95_s: unknown SLO class {cls!r} "
                    f"(one of {SLO_CLASS_NAMES})"
                )
            if target <= 0:
                raise ValueError(
                    f"ttft_p95_s: target for {cls!r} must be > 0 "
                    "(omit the class for no target)"
                )
        if self.token_latency_p95_s < 0:
            raise ValueError("token_latency_p95_s must be >= 0 (0 = off)")
        if not 0.0 <= self.availability_target < 1.0:
            raise ValueError(
                "availability_target must be in [0, 1) — 0 disables, "
                "1.0 would allow no failures ever (an unpayable budget)"
            )
        if self.min_samples < 1:
            raise ValueError("min_samples must be >= 1")

    def ttft_target_map(self) -> dict[str, float]:
        return _parse_tenant_map(self.ttft_p95_s, "ttft_p95_s")


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Closed-loop fleet elasticity (serve/autoscale.py; docs/autoscale.md
    has the interlock table and stagger math). Off by default — the fleet
    then stays at the static ``replicas`` count, exactly the pre-autoscale
    behaviour.

    Enabled, a ``FleetAutoscaler`` control loop polls the signals the repo
    already trusts under chaos — SLO burn rate (obs/slo.py), queue depth
    watermarks, and the brownout pressure level (runtime/pressure.py) —
    and drives ``add_replica``/``remove_replica(drain=True)`` between
    ``min``/``max``, with anti-flap machinery (consecutive-poll
    confirmation, separate grow/shrink cooldowns) and hard interlocks
    (never grow at shed-or-above pressure, never shrink below min or over
    an in-flight drain, WAL replay completes before the first decision).
    The same config carries the sweep-phase stagger controller: replicas
    hold at their shard-0 boundary (bounded) until their sweep offsets sit
    at i/N, so worst-case admission wait drops to sweep/N."""

    enabled: bool = False
    # Fleet size bounds the controller may move between. The static
    # ``--replicas`` count is the starting population and must sit inside
    # [min, max] (cross-validated by ServeConfig).
    min: int = 1
    max: int = 4
    # Controller poll interval (seconds) — decisions are made at most
    # once per poll, and confirmation counts in polls.
    poll_s: float = 1.0
    # Grow when the worst per-class SLO burn rate sustains at or above
    # this (burn 1.0 = spending the whole error budget) OR the queue
    # depth fraction sustains at or above grow_queue_frac.
    grow_burn_rate: float = 1.0
    grow_queue_frac: float = 0.75
    # Shrink only when burn AND queue are BOTH below these (hysteresis:
    # the shrink thresholds sit well under the grow ones, so a reading
    # between the bands holds steady instead of oscillating).
    shrink_burn_rate: float = 0.25
    shrink_queue_frac: float = 0.10
    # A breach must persist this many CONSECUTIVE polls before acting —
    # a single spiky sample never scales the fleet.
    confirm_polls: int = 3
    # Per-direction cooldowns (seconds) after ANY scale action: grow
    # again only after grow_cooldown_s, shrink only after
    # shrink_cooldown_s (shrink waits longer by default — capacity is
    # cheap to hold and expensive to miss).
    grow_cooldown_s: float = 10.0
    shrink_cooldown_s: float = 30.0
    # Journal every decision without acting (autoscale_* events carry
    # dry_run=True) — the shadow-mode rehearsal before trusting the loop.
    dry_run: bool = False
    # --- sweep-phase stagger (ROADMAP item 4: sweep/N admission wait) ---
    # Control replica sweep offsets to i/N via bounded boundary holds.
    stagger: bool = True
    # Normalized stagger error (0 = perfect i/N spread, 1 = all replicas
    # in phase) at or under this counts as converged; the controller only
    # injects holds while above it.
    stagger_tolerance: float = 0.15
    # Per-boundary hold cap as a fraction of one measured sweep wall —
    # a hold can never stall a replica longer than this per sweep.
    stagger_hold_max_frac: float = 0.5

    def __post_init__(self) -> None:
        if self.min < 1:
            raise ValueError("autoscale min must be >= 1")
        if self.max < self.min:
            raise ValueError("autoscale max must be >= min")
        if self.poll_s <= 0:
            raise ValueError("autoscale poll_s must be > 0")
        if self.grow_burn_rate < 0 or self.shrink_burn_rate < 0:
            raise ValueError("autoscale burn-rate thresholds must be >= 0")
        if self.shrink_burn_rate > self.grow_burn_rate:
            raise ValueError(
                "autoscale shrink_burn_rate must be <= grow_burn_rate "
                "(the hysteresis band would invert)"
            )
        for name in ("grow_queue_frac", "shrink_queue_frac"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"autoscale {name} must be in [0, 1]")
        if self.shrink_queue_frac > self.grow_queue_frac:
            raise ValueError(
                "autoscale shrink_queue_frac must be <= grow_queue_frac "
                "(the hysteresis band would invert)"
            )
        if self.confirm_polls < 1:
            raise ValueError("autoscale confirm_polls must be >= 1")
        if self.grow_cooldown_s < 0 or self.shrink_cooldown_s < 0:
            raise ValueError("autoscale cooldowns must be >= 0")
        if not 0.0 < self.stagger_tolerance <= 1.0:
            raise ValueError(
                "autoscale stagger_tolerance must be in (0, 1]"
            )
        if not 0.0 <= self.stagger_hold_max_frac <= 1.0:
            raise ValueError(
                "autoscale stagger_hold_max_frac must be in [0, 1]"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Online-serving knobs (the ``serve`` CLI subcommand / serve.engine).

    The offline flags (FrameworkConfig) describe ONE batch run; these
    describe the server wrapped around the same runtime: how many requests
    may wait (admission queue), how many join per wave at a shard-0
    boundary, and how long a request may sit queued before it is evicted.
    """

    # Admission queue capacity: submissions beyond this are rejected
    # immediately with a reason (backpressure) instead of queueing unbounded.
    queue_capacity: int = 64
    # Most requests coalesced into ONE wave at a shard-0 boundary — the
    # prefill batch size. A wave's blocks ride every subsequent sweep, so
    # the knob bounds per-wave prefill latency AND per-sweep KV footprint.
    max_wave_requests: int = 8
    # Total in-flight requests across all active waves; the batcher stops
    # admitting (requests keep queueing) until completions free slots.
    max_active_requests: int = 32
    # Per-request generation budget when the request doesn't name one.
    default_max_new_tokens: int = 16
    # Queue-wait deadline (seconds) applied to requests that don't carry
    # their own: a request not ADMITTED within this window is evicted with
    # status 'expired' (0 = no deadline). Time-to-first-token is the online
    # contract; serving a long-expired request wastes sweeps the live ones
    # need.
    default_deadline_s: float = 0.0
    # Engine idle poll (seconds) while no wave is active and the queue is
    # empty. Admission itself is boundary-driven, not polled: with waves in
    # flight the queue is re-checked at every shard-0 boundary.
    idle_poll_s: float = 0.01
    # Periodic structured stats line (JSON to stderr) every this many
    # seconds; 0 = off. Snapshot of queue depth, active requests, TTFT and
    # per-token latency summaries, admitted/rejected/expired counters.
    stats_interval_s: float = 0.0
    # Step-progress watchdog (streamed-weights mode): if a sweep makes no
    # shard progress for this many seconds, the engine aborts the weight
    # source, fails ONLY the in-flight waves (their futures resolve with a
    # structured WaveAborted instead of hanging forever), restarts the
    # source, and keeps serving. 0 = off.
    watchdog_abort_s: float = 0.0
    # Prometheus metrics endpoint (obs/registry.py MetricsServer): serve
    # /metrics (text exposition) and /metrics.json on 127.0.0.1 at this
    # port — queue depth, TTFT quantiles, streamed bytes, cache hit rate,
    # residency savings, retry/heal/recovery counters in one scrape.
    # None = off; 0 = bind an ephemeral port (tests/parallel engines; the
    # bound port is engine.metrics_server.port).
    metrics_port: int | None = None
    # --- replica fleet (serve/fleet.py; engaged by the CLI when > 1) ---
    # N ServeEngine replicas behind a shard-phase-aware router: each runs
    # its own sweep thread, all share the process host shard cache (a
    # recycled replica re-warms instantly). Requests dispatch to the
    # healthiest replica; a dead replica's queued and in-flight requests
    # re-dispatch to a survivor exactly once, token-identically.
    replicas: int = 1
    # Router score = phase_weight * boundary_frac + depth_weight * load
    # (serve/router.py): boundary_frac is the fraction of a sweep left
    # until the replica's next shard-0 admission point, load its
    # (queued + active) / max_active_requests. Lowest score wins.
    router_phase_weight: float = 1.0
    router_depth_weight: float = 1.0
    # Fleet health-monitor poll interval (seconds): each tick reads every
    # replica's registry health (engine_recoveries, watchdog stalls) and
    # sweep-progress watermark; a busy replica whose watermark stalls past
    # watchdog_abort_s is declared dead and hard-failed (watchdog_abort_s
    # 0 disables the liveness check, as for the in-engine watchdog).
    router_health_poll_s: float = 0.2
    # Auto-drain threshold: a replica whose engine_recoveries counter
    # (the PR 3 degrade path firing repeatedly — a flaky-but-alive
    # engine) reaches this is gracefully drained and recycled. 0 = off.
    router_drain_recoveries: int = 0
    # Admission-side request size cap: a request whose estimated prompt
    # tokens (longest suffix included) plus its max_new_tokens budget
    # exceeds this is rejected at SUBMIT time with a typed
    # RequestTooLarge — instead of first failing at allocation inside
    # the wave (where an oversized request's MemoryError previously
    # aborted the whole wave it joined). 0 = off.
    max_request_tokens: int = 0
    # Speculative decoding on the serving path (docs/speculative.md):
    # each in-flight request carries its own prompt-lookup draft stream,
    # and every decode sweep verifies all drafts batch-wide in ONE
    # K+1-slot pass (runtime/decode.SpecVerifier) — a sweep costs the
    # same whether it advances each request by 1 token or by k accepted
    # tokens, so acceptance multiplies tokens-per-sweep directly. Output
    # stays greedy-exact (token-identical to speculative_k=0, which
    # remains the default and the non-speculative fast path). Composes
    # with sched preemption (draft state truncates to the resume
    # watermark; resume tokens fold into the draft context), prefix
    # coalescing (coalesced entries draft per-suffix), and the fleet
    # (re-dispatch restarts generation, greedy-exact either way).
    speculative_k: int = 0
    # --- resident draft model + adaptive k (runtime/draft.py,
    # serve/spec.py; docs/speculative.md) -------------------------------
    # Checkpoint directory of a SMALL draft model pinned whole on chip
    # through a dedicated residency tier ("" = off, keep prompt-lookup
    # drafting). Draft decode runs entirely against the pinned weights:
    # zero bytes added to the per-sweep host→HBM stream. Output stays
    # token-identical whatever the draft model proposes.
    draft_model_path: str = ""
    # Close the loop: adapt per-SLO-class draft depth k from windowed
    # live acceptance (raise while drafts land, shrink while they miss),
    # fund interactive-class rows first, and back k off to 0 as the
    # brownout ladder's first lever (runtime/pressure.py spec_backoff).
    # Requires speculative_k > 0 (the starting k) — the slot budget is
    # provisioned at spec_k_max so k can grow without re-planning waves.
    spec_adaptive: bool = False
    # Adaptive-k bounds: per-class k stays in [spec_k_min, spec_k_max].
    spec_k_min: int = 0
    spec_k_max: int = 8
    # Acceptance window: a class's k moves only after this many observed
    # drafting passes, comparing windowed acceptance against the two
    # thresholds (raise at >= spec_raise_threshold, shrink at
    # <= spec_backoff_threshold; in between holds).
    spec_window: int = 8
    spec_raise_threshold: float = 0.6
    spec_backoff_threshold: float = 0.2
    # Per-pass draft-token budget across the wave (0 = unlimited):
    # rows are funded in strict SLO-class priority order, so under a
    # budget best-effort drafts are the first to go.
    spec_draft_budget: int = 0
    # Multi-tenant sweep scheduler (serve/sched/; --sched* flags): SLO
    # classes with strict priority + sweep-boundary preemption,
    # per-tenant fair queueing and rate limits, prefix coalescing. Off
    # by default — the queue then pops strict FIFO.
    sched: SchedConfig = dataclasses.field(default_factory=SchedConfig)
    # SLO targets + error budgets (obs/slo.py; --slo* flags): per-class
    # p95 TTFT targets, an aggregate token-latency target, and an
    # availability target over the per-class latency streams PR 12
    # exports — burn-rate/remaining-budget gauges (fls_slo_*) plus a
    # journal event (and, armed, an incident bundle) on exhaustion.
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    # Closed-loop fleet elasticity + sweep-phase stagger
    # (serve/autoscale.py; --autoscale* flags): an SLO-burn/queue/
    # pressure-driven controller moves the fleet between autoscale.min
    # and autoscale.max with anti-flap hysteresis and hard interlocks,
    # and holds replica sweep offsets at i/N so worst-case admission
    # wait stays sweep/N. Off by default — the fleet stays at
    # ``replicas`` and phases drift free, the pre-autoscale behaviour.
    autoscale: AutoscaleConfig = dataclasses.field(
        default_factory=AutoscaleConfig
    )
    # --- crash-safe serving (serve/wal.py + serve/recovery.py) ---------
    # Durable request WAL directory ("" = off, the default): every
    # admission/progress/terminal transition appends a crc-framed record;
    # after a process death, startup replay re-admits every unfinished
    # request and serves it token-identically (greedy decode replays
    # bit-for-bit). Fleet mode shares ONE log across replicas.
    wal_dir: str = ""
    # WAL durability policy: "always" fsyncs every record; "admit" (the
    # default) fsyncs admission + terminal records only — progress is
    # recomputable, so losing it to a power cut costs re-decode work,
    # never correctness; "never" flushes to the kernel only (full
    # process-crash durability; machine-crash durability delegated to the
    # filesystem). Every record is flushed either way: SIGKILL loses at
    # most the record in flight.
    wal_fsync: str = "admit"
    # Segment rotation threshold (MB): sealed segments whose every
    # mentioned request id is terminal are compacted (deleted).
    wal_max_mb: float = 64.0

    def __post_init__(self) -> None:
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        if self.max_wave_requests < 1:
            raise ValueError("max_wave_requests must be >= 1")
        if self.max_active_requests < self.max_wave_requests:
            raise ValueError(
                "max_active_requests must be >= max_wave_requests"
            )
        if self.default_max_new_tokens < 1:
            raise ValueError("default_max_new_tokens must be >= 1")
        if self.default_deadline_s < 0:
            raise ValueError("default_deadline_s must be >= 0")
        if self.idle_poll_s <= 0:
            raise ValueError("idle_poll_s must be > 0")
        if self.stats_interval_s < 0:
            raise ValueError("stats_interval_s must be >= 0")
        if self.watchdog_abort_s < 0:
            raise ValueError("watchdog_abort_s must be >= 0")
        if self.metrics_port is not None and not 0 <= self.metrics_port <= 65535:
            raise ValueError(
                "metrics_port must be in [0, 65535] (or None for off), "
                f"got {self.metrics_port}"
            )
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.router_phase_weight < 0 or self.router_depth_weight < 0:
            raise ValueError(
                "router_phase_weight/router_depth_weight must be >= 0"
            )
        if self.router_health_poll_s <= 0:
            raise ValueError("router_health_poll_s must be > 0")
        if self.router_drain_recoveries < 0:
            raise ValueError("router_drain_recoveries must be >= 0 (0 = off)")
        if self.max_request_tokens < 0:
            raise ValueError("max_request_tokens must be >= 0 (0 = off)")
        if not 0 <= self.speculative_k <= 64:
            raise ValueError(
                "ServeConfig.speculative_k must be in [0, 64], got "
                f"{self.speculative_k}"
            )
        if self.spec_adaptive and self.speculative_k < 1:
            raise ValueError(
                "spec_adaptive requires speculative_k >= 1 (the starting "
                "draft depth)"
            )
        if not 0 <= self.spec_k_min <= self.spec_k_max <= 64:
            raise ValueError(
                "need 0 <= spec_k_min <= spec_k_max <= 64, got "
                f"[{self.spec_k_min}, {self.spec_k_max}]"
            )
        if self.spec_adaptive and not (
            self.spec_k_min <= self.speculative_k <= self.spec_k_max
        ):
            raise ValueError(
                "speculative_k must sit inside [spec_k_min, spec_k_max] "
                f"when spec_adaptive is on, got k={self.speculative_k} "
                f"bounds=[{self.spec_k_min}, {self.spec_k_max}]"
            )
        if self.spec_window < 1:
            raise ValueError("spec_window must be >= 1")
        if not (
            0.0 <= self.spec_backoff_threshold
            <= self.spec_raise_threshold <= 1.0
        ):
            raise ValueError(
                "need 0 <= spec_backoff_threshold <= spec_raise_threshold "
                f"<= 1, got backoff={self.spec_backoff_threshold} "
                f"raise={self.spec_raise_threshold}"
            )
        if self.spec_draft_budget < 0:
            raise ValueError("spec_draft_budget must be >= 0 (0 = unlimited)")
        if self.autoscale.enabled and not (
            self.autoscale.min <= self.replicas <= self.autoscale.max
        ):
            raise ValueError(
                "replicas must sit inside [autoscale.min, autoscale.max] "
                f"when autoscaling is enabled, got replicas={self.replicas} "
                f"bounds=[{self.autoscale.min}, {self.autoscale.max}]"
            )
        if self.wal_fsync not in ("always", "admit", "never"):
            raise ValueError(
                "wal_fsync must be one of 'always'/'admit'/'never', got "
                f"{self.wal_fsync!r}"
            )
        if self.wal_max_mb <= 0:
            raise ValueError("wal_max_mb must be > 0")
