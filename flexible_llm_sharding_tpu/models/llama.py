"""Pure-function Llama, matching HF `LlamaForCausalLM` numerics.

The reference drives transformers' `LlamaDecoderLayer` on a meta-device
skeleton and materialises weights per layer
(``/root/reference/utils.py:109-131``). TPU-first redesign (SURVEY.md §7):
layers are *pure functions* over parameter pytrees — nothing is ever
"installed" into a module; weights are arguments, so streaming a layer is
just passing a different pytree, and XLA compiles one program per shape
family that is reused for all layers.

Three forward entry points:

- :func:`prefix_suffix_layer` — the streaming scorer step for one prompt:
  prefix runs once producing its KV, all suffix continuations attend to the
  shared prefix KV in one batched call. This is the reference's prefix-KV
  expand trick (``/root/reference/utils.py:266-279``) as a single fused
  jittable function.
- :func:`decoder_layer` — a plain batched layer (monolithic forward /
  training path).
- :func:`forward_full` — whole-model forward for golden tests and training.

Parameter pytree layout (all linear kernels stored [in, out], i.e. the
transpose of HF's [out, in], so matmuls need no transposes on device):

    params = {
      'embed':  {'embedding': [V, D]},
      'layers': [ per-layer dicts ... ]     # or stacked with leading axis
      'norm':   {'scale': [D]},
      'lm_head': {'kernel': [D, V]},        # absent if tied embeddings
    }
    layer = {
      'input_layernorm': {'scale': [D]},
      'post_attention_layernorm': {'scale': [D]},
      'attn': {'wq': [D, nq*hd], 'wk': [D, nkv*hd],
               'wv': [D, nkv*hd], 'wo': [nq*hd, D]},
      'mlp':  {'gate': [D, F], 'up': [D, F], 'down': [F, D]},
    }
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from flexible_llm_sharding_tpu.config import SUPPORTED_ACTIVATIONS, LlamaConfig
from flexible_llm_sharding_tpu.ops import (
    apply_rope,
    apply_rope_interleaved,
    attention,
    rms_norm,
    rope_cos_sin,
)
from flexible_llm_sharding_tpu.ops import (
    grouped_matmul,
    kda_attention,
    lightning_attention,
    pallas_attention,
)
from flexible_llm_sharding_tpu.ops.attention import (
    causal_mask,
    decode_attention,
    prefix_shared_attention,
)

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

# HIGHEST is a no-op for bf16/fp16 operands (the production dtype — MXU native)
# but keeps float32 matmuls genuinely float32: XLA's default otherwise lowers
# fp32 matmuls to reduced precision, which breaks HF-numerics parity.
_PRECISION = jax.lax.Precision.HIGHEST


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(x, w.astype(x.dtype), precision=_PRECISION)


def _lin(x: jax.Array, params: Params, w: str, b: str) -> jax.Array:
    """Linear with optional bias. Bias keys exist only when the model family
    uses them (Qwen2 q/k/v, Llama attention_bias/mlp_bias) — presence is a
    trace-time structural fact, so unbiased models pay nothing."""
    y = _mm(x, params[w])
    if b in params:
        y = y + params[b].astype(y.dtype)
    return y


def layer_kind(cfg: LlamaConfig, attn: Params, sliding):
    """((heads, kv heads, qk dim, v dim), sliding) of the layer whose
    attention weights are ``attn``. A model with ONE attention shape answers
    from its config and hands ``sliding`` back as given. A model whose local
    layers have another shape than its full ones (``cfg.local_attn_shape``:
    MiMo-V2) is answered by the weights: the projections' widths say which
    kind this layer is, so ``sliding`` comes back as a python bool (static)
    whatever traced flag the caller held. A linear-attention layer
    (``is_linear``) answers with the linear shape and ``sliding`` as given."""
    if is_linear(cfg, attn):
        return cfg.attn_shape(linear=True), sliding
    full, local = cfg.attn_shape(False), cfg.attn_shape(True)
    if full == local:
        return full, sliding
    widths = tuple(attn[w].shape[-1] for w in ("wq", "wk", "wv"))
    for (nq, nkv, hd, vd), is_local in ((full, False), (local, True)):
        if widths == (nq * hd, nkv * hd, nkv * vd):
            return (nq, nkv, hd, vd), is_local
    raise ValueError(
        f"attention projections of widths {widths} fit neither the full "
        f"{full} nor the local {local} (heads, kv heads, qk, v) shape"
    )


def is_linear(cfg: LlamaConfig, attn: Params) -> bool:
    """Whether the layer whose attention weights are ``attn`` is a linear-
    attention layer of a model that has them (``cfg.layer_linear``): told by
    the widths of its key and value projections where the two kinds differ
    in shape, else by the output norm only that kind carries. Static: a
    layer's kind is a fact of its weights' shapes, so a jitted step compiles
    once a kind, not once a layer."""
    if cfg.layer_linear is None:
        return False
    if cfg.linear_kind == "kda":  # only that kind has a decay's weights
        return "A_log" in attn
    widths = lambda shape: (shape[1] * shape[2], shape[1] * shape[3])
    linear, full = cfg.attn_shape(linear=True), cfg.attn_shape()
    if widths(linear) != widths(full):
        return (attn["wk"].shape[-1], attn["wv"].shape[-1]) == widths(linear)
    return "o_norm" in attn


def layer_log_decay(cfg: LlamaConfig) -> np.ndarray | None:
    """float32 [layers, linear heads]: each linear-attention layer's per-head
    log-decay (its softmax layers' rows are 0 and unread); None for a model
    without such layers. Lightning Attention-2's per-head slopes with
    MiniMax-01's per-layer factor: head n of H in layer l of L decays at
    ``2^(-8 (n + 1) / H) * (1 - l / (L - 1) + 1e-5)`` a token. No tensor of a
    checkpoint: a function of the layer's index among all layers, which is
    why it reaches a layer as an argument."""
    if cfg.layer_linear is None or cfg.linear_kind != "lightning":
        return None  # a KDA layer's decay is its weights' and its input's
    h, n = cfg.linear_attn_shape[0], cfg.num_hidden_layers
    slopes = 2.0 ** (-8.0 * (np.arange(h) + 1) / h)
    factor = 1.0 - np.arange(n) / max(n - 1, 1) + 1e-5
    table = -slopes[None, :] * factor[:, None]
    return np.where(np.asarray(cfg.layer_linear)[:, None], table, 0.0).astype(np.float32)


def layer_swiglu_limits(cfg: LlamaConfig) -> np.ndarray | None:
    """float32 [layers, 2]: each decoder layer's SwiGLU clamp of its routed
    experts and of its shared expert (a dense layer's MLP takes the second),
    +inf where the config's list reads 0 (no clamp); a model with one
    ``swiglu_limit`` has it in every entry; None for a model that clamps
    nothing. Like ``layer_log_decay``, a layer gets its row as an argument,
    picked by its index, so that one program serves every layer of a kind."""
    n = cfg.num_hidden_layers
    if cfg.expert_swiglu_limits is None and cfg.shared_swiglu_limits is None:
        if cfg.swiglu_limit is None:
            return None
        return np.full((n, 2), cfg.swiglu_limit, np.float32)
    table = np.asarray(
        [cfg.expert_swiglu_limits or (0.0,) * n, cfg.shared_swiglu_limits or (0.0,) * n],
        np.float32,
    ).T
    return np.where(table > 0, table, np.inf).astype(np.float32)


def layer_indexed(cfg: LlamaConfig) -> bool:
    """Whether a decoder layer reads its row of a per-layer table
    (``layer_log_decay``, ``layer_swiglu_limits``): a segment of such a
    model's layers then carries their indices."""
    return layer_log_decay(cfg) is not None or layer_swiglu_limits(cfg) is not None


@jax.named_scope("qkv")
def _qkv(attn: Params, cfg: LlamaConfig, x: jax.Array, shape=None):
    """x: [..., L, D] -> q [..., L, n_q, hd], k [..., L, n_kv, hd],
    v [..., L, n_kv, vd]. ``shape``: the layer kind's (heads, kv heads, qk
    dim, v dim) from ``layer_kind``; None = the config's one shape."""
    nq, nkv, hd, vd = shape or cfg.attn_shape()
    q = _lin(x, attn, "wq", "bq").reshape(*x.shape[:-1], nq, hd)
    k = _lin(x, attn, "wk", "bk").reshape(*x.shape[:-1], nkv, hd)
    v = _lin(x, attn, "wv", "bv").reshape(*x.shape[:-1], nkv, vd)
    if "q_norm" in attn:
        # Per-head-dim RMSNorm on q/k, pre-RoPE (Qwen3 llama-style; Gemma3
        # (1+w)-style — the family's norm_unit_offset covers both).
        q = rms_norm(q, attn["q_norm"], cfg.rms_norm_eps, cfg.norm_unit_offset)
        k = rms_norm(k, attn["k_norm"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    return q, k, v


def _out_proj(attn: Params, o: jax.Array) -> jax.Array:
    """o: [..., L, n_q, hd] -> [..., L, D]."""
    return _lin(o.reshape(*o.shape[:-2], -1), attn, "wo", "bo")


@jax.named_scope("mla_qkv")
def _qkv_mla(attn: Params, cfg: LlamaConfig, x: jax.Array, positions, total_len=None):
    """Multi-head latent attention q/k/v assembly (DeepSeek-V2/V3,
    DeepseekV3Attention): queries optionally LoRA'd (q_a -> norm -> q_b),
    KV compressed to ``kv_lora_rank`` channels plus ONE shared
    ``qk_rope_head_dim`` rope key, decompressed per head (kv_b) into
    ``qk_nope_head_dim`` keys and ``v_head_dim`` values. Rope applies only
    to the rot slices (interleaved complex-pair convention when
    ``cfg.rope_interleaved``); the shared rope key broadcasts across heads.
    Returns q/k [..., L, H, qk_nope+qk_rope], v [..., L, H, v_head_dim] —
    the downstream attention ops are head-dim-agnostic, so the usual GQA
    machinery runs unchanged with n_kv == n_heads.
    """
    if cfg.rope_local_theta is not None or cfg.layer_rope is not None:
        # No named family composes MLA with per-layer rope bases or NoPE
        # patterns; silently applying one global base would drop declared
        # numerics — fail loudly instead.
        raise NotImplementedError(
            "MLA does not compose with rope_local_theta / layer_rope"
        )
    nh = cfg.num_attention_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv = cfg.v_dim
    eps = cfg.rms_norm_eps
    if "q_a" in attn:
        q = _mm(
            rms_norm(_lin(x, attn, "q_a", "bq_a"), attn["q_a_norm"], eps, False),
            attn["q_b"],
        )
    else:
        q = _mm(x, attn["wq"])  # HF's dense q_proj is bias-free
    q = q.reshape(*x.shape[:-1], nh, dn + dr)
    ckv = _lin(x, attn, "kv_a", "bkv_a")  # [..., L, kv_lora + dr]
    c_kv, k_rot = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank :]
    kv = _mm(
        rms_norm(c_kv, attn["kv_a_norm"], eps, False), attn["kv_b"]
    ).reshape(*x.shape[:-1], nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if not dr:  # no rotary part at all (GLM-5.3's ``mla_use_nope``)
        return q, k_nope, v

    cos, sin = rope_cos_sin(
        positions, dr, cfg.rope_theta, cfg.rope_scaling_spec, total_len=total_len
    )
    rot = apply_rope_interleaved if cfg.rope_interleaved else apply_rope
    if "q_norm" in attn:
        # Ling-3.0: q and each head's whole key (its own nope part and the
        # shared rope key) RMS-normed over all qk dims before rotary, which
        # then turns every head's rope part on its own.
        with jax.named_scope("qk_norm"):
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rot[..., None, :], (*k_nope.shape[:-1], dr))],
                axis=-1,
            )
            q = rms_norm(q, attn["q_norm"], eps, False)
            k = rms_norm(k, attn["k_norm"], eps, False)
        q = jnp.concatenate([q[..., :dn], rot(q[..., dn:], cos, sin)], axis=-1)
        k = jnp.concatenate([k[..., :dn], rot(k[..., dn:], cos, sin)], axis=-1)
        return q, k, v
    q_rot = rot(q[..., dn:], cos, sin)
    k_rot = rot(k_rot[..., None, :], cos, sin)  # [..., L, 1, dr] shared head
    q = jnp.concatenate([q[..., :dn], q_rot], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rot, (*k_nope.shape[:-1], dr))], axis=-1
    )
    return q, k, v


def positioned_qkv(
    params: Params, cfg: LlamaConfig, h: jax.Array, positions, sliding,
    rope_on, total_len=None,
):
    """Post-rope q/k/v for one layer — the single integration point the
    layer fns share: standard families run _qkv + position_qk; MLA
    (``cfg.kv_lora_rank``) runs its own assembly (partial rope, shared
    rope key, distinct value dim)."""
    if cfg.kv_lora_rank:
        return _qkv_mla(params["attn"], cfg, h, positions, total_len)
    shape, sliding = layer_kind(cfg, params["attn"], sliding)
    q, k, v = _qkv(params["attn"], cfg, h, shape)
    q, k = position_qk(cfg, q, k, positions, sliding, rope_on, total_len)
    if cfg.attn_value_scale is not None:
        v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    return q, k, v


# MLP gate activations by config.hidden_act; HF's 'gelu' is the exact erf
# form, 'gelu_pytorch_tanh' (gemma) the tanh approximation.
_ACT = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}
assert set(_ACT) == set(SUPPORTED_ACTIVATIONS)  # config validates against this


def _glu(act, gate: jax.Array, up, limit: jax.Array | None) -> jax.Array:
    """``act(gate) * up()``, and under a clamp L ``act(min(gate, L)) *
    clip(up(), -L, L)``. ``up`` is a thunk, called after the gate's
    activation: the order every unclamped model's program was traced in.
    ``limit``: None, or a scalar array (a layer's entry of
    ``layer_swiglu_limits``; +inf clamps nothing)."""
    if limit is None:
        return act(gate) * up()
    with jax.named_scope("swiglu_clamp"):
        limit = limit.astype(gate.dtype)
        return act(jnp.minimum(gate, limit)) * jnp.clip(up(), -limit, limit)


def _clamp_row(cfg: LlamaConfig | None, limits):
    """A layer's row of ``layer_swiglu_limits``: ``limits`` where given,
    else the row every layer shares in a model with one clamp; None for a
    model that clamps nothing. A model whose clamps differ by layer has to
    be given its row."""
    if limits is not None or cfg is None:
        return limits
    table = layer_swiglu_limits(cfg)
    if table is None:
        return None
    if (table != table[0]).any():
        raise ValueError(
            "a layer of a model whose SwiGLU clamps differ by layer needs its "
            "row of layer_swiglu_limits"
        )
    return jnp.asarray(table[0])


@jax.named_scope("mlp")
def _dense_mlp(mlp: Params, x: jax.Array, act, limit: jax.Array | None = None) -> jax.Array:
    h = _glu(act, _lin(x, mlp, "gate", "bgate"), lambda: _lin(x, mlp, "up", "bup"), limit)
    return _lin(h, mlp, "down", "bdown")


def _moe_mlp(mlp: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    """Mixture-of-experts MLP (Mixtral), HF-parity routing.

    Routing matches ``MixtralSparseMoeBlock``: softmax over ALL experts in
    float32, top-k of those probabilities, renormalised by their sum, cast to
    the input dtype, applied to each expert's FFN output.

    TPU-first compute layout: experts are stacked arrays ``gate/up [E, D, F]``,
    ``down [E, F, D]`` and every expert runs on every token (one batched
    einsum per projection, MXU-shaped) with the combine weights zeroing the
    non-selected experts: no gather/scatter or ragged shape, at E/k times the
    FLOPs the tokens need. That surplus is not free in the streaming regime:
    in the sigmoid-router families' cells it was 43-75% of a sweep (ledger,
    PR 27), which is why ``_routed_experts`` exists; this family has not
    moved onto it (ROADMAP D12). Under expert parallelism (``layer_specs``) the stacked
    E axis is sharded over the mesh, so each chip computes only its own
    experts and GSPMD inserts one psum for the combine — the reference has no
    MoE at all (dense Llama only, SURVEY.md §2.2 'EP: absent').
    """
    e, k = cfg.num_local_experts, cfg.num_experts_per_tok
    with jax.named_scope("moe_router"):
        logits = _mm(x, mlp["router"])  # [..., L, E], model dtype (HF gate dtype)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, k)  # sorted desc, like torch.topk
        if cfg.moe_norm_topk_prob:  # Mixtral always; Qwen3-MoE per norm_topk_prob
            top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
        # Scatter the k renormalised weights back onto the E axis.
        combine = jnp.sum(
            jax.nn.one_hot(top_idx, e, dtype=jnp.float32) * top_vals[..., None], axis=-2
        ).astype(x.dtype)  # [..., L, E]
    with jax.named_scope("moe_experts"):
        h = _ACT[cfg.hidden_act](
            jnp.einsum("...ld,edf->...lef", x, mlp["gate"].astype(x.dtype), precision=_PRECISION)
        ) * jnp.einsum("...ld,edf->...lef", x, mlp["up"].astype(x.dtype), precision=_PRECISION)
        # Fold the combine weights in BEFORE the down projection (scalar per
        # token-expert, so algebraically identical to HF's weight-after-w2) and
        # hard-zero non-selected experts with `where`: a plain `h * 0` would turn
        # an fp16 overflow (inf) in an expert the router never picked into NaN —
        # a failure HF can't have, since it never computes unselected experts.
        # This also avoids materialising a [..., L, E, D] per-expert output.
        c = combine[..., None]  # [..., L, E, 1]
        h = jnp.where(c != 0, h * c, jnp.zeros_like(h))
        return jnp.einsum("...lef,efd->...ld", h, mlp["down"].astype(x.dtype), precision=_PRECISION)


def _llama4_moe_mlp(mlp: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    """Llama4's MoE: shared expert + top-k routed experts whose INPUT is
    scaled by the sigmoid of the routed logit (HF Llama4TextMoe/Llama4Router:
    top-k logits scattered into -inf, sigmoid in fp32, multiplied into the
    hidden states BEFORE the expert FFN — unlike Mixtral's output weighting).
    Same compute-all einsum layout as the Mixtral path; zero-scaled expert
    inputs are hard-zeroed so they can't overflow."""
    e, k = cfg.num_local_experts, cfg.num_experts_per_tok
    act = _ACT[cfg.hidden_act]
    with jax.named_scope("moe_router"):
        logits = _mm(x, mlp["router"])  # [..., L, E]
        top_vals, top_idx = jax.lax.top_k(logits.astype(jnp.float32), k)
        c = jnp.sum(
            jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
            * jax.nn.sigmoid(top_vals)[..., None],
            axis=-2,
        ).astype(x.dtype)  # [..., L, E]
    with jax.named_scope("moe_experts"):
        xin = x[..., None, :] * c[..., None]  # [..., L, E, D]
        xin = jnp.where(c[..., None] != 0, xin, jnp.zeros_like(xin))
        h = act(
            jnp.einsum("...led,edf->...lef", xin, mlp["gate"].astype(x.dtype), precision=_PRECISION)
        ) * jnp.einsum("...led,edf->...lef", xin, mlp["up"].astype(x.dtype), precision=_PRECISION)
        routed = jnp.einsum(
            "...lef,efd->...ld", h, mlp["down"].astype(x.dtype), precision=_PRECISION
        )  # contracts e AND f: sums the experts
    with jax.named_scope("moe_shared_experts"):
        shared = _mm(
            act(_mm(x, mlp["shared_gate"])) * _mm(x, mlp["shared_up"]), mlp["shared_down"]
        )
    return shared + routed


def _routed_experts(
    x: jax.Array,
    top_idx: jax.Array,
    top_w: jax.Array,
    gate: jax.Array,
    up: jax.Array,
    down: jax.Array,
    held: range,
    act,
    use_pallas: bool = False,
    limit: jax.Array | None = None,
    tiles: list | None = None,
) -> jax.Array:
    """The routed experts' part of an MoE layer, computing a row only in the
    experts its router chose: rows ``x [R, D]`` with the router's choices
    ``top_idx [R, k]`` (ids over the router's width) and combine weights
    ``top_w [R, k]``, over the stacked experts this process holds
    (``gate/up [H, D, F]``, ``down [H, F, D]``: the ids ``held`` of the
    router's width) -> ``[R, D]``. ``tiles`` (a list): gets one int32 [2]
    array, (the rows the grouped matmuls' (expert, row tile) visits cover,
    the held experts given at least one row).

    The R*k (row, choice) assignments are sorted by held expert and the three
    projections run as grouped matmuls over the sorted rows
    (``ops/grouped_matmul.py``: ``jax.lax.ragged_dot``, or with
    ``use_pallas`` the Pallas kernel where the shapes are eligible). An
    assignment whose expert is not held sorts past the last group; nothing is
    trusted of such rows (``where``, as the compute-all body zeroes what the
    router did not choose). The weight goes in before the down projection and
    a row's k results add up in float32, rounded once, as in the einsum that
    contracts experts and width together. Not traceable under ``jax.vmap``
    with per-example groups: callers hold flat rows."""
    (r, k), h = top_idx.shape, gate.shape[0]
    n = r * k
    local = top_idx.astype(jnp.int32) - held.start
    on_held = ((local >= 0) & (local < h)).T  # [k, R]
    # Assignments choice-major (a = choice * R + row), so that a row's k
    # results come back as k slabs of [R, D] and not as [R, k, D], whose
    # k-row tiles the chip would pad and copy.
    local = jnp.where(on_held, local.T, h).reshape(n)
    # Stable sort by held expert, the combine weights riding along: `order`
    # lists the assignments as the grouped matmuls see them, `inv` finds an
    # assignment in that order.
    slot = jnp.arange(n, dtype=jnp.int32)
    _, order, ws = jax.lax.sort(
        (local, slot, top_w.T.reshape(n)), num_keys=1, is_stable=True
    )
    _, inv = jax.lax.sort((order, slot), num_keys=1)
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(h, dtype=jnp.int32), axis=0, dtype=jnp.int32
    )
    if tiles is not None:
        tiles.append(jnp.stack([
            grouped_matmul.tile_visits(group_sizes) * grouped_matmul.ROW_TILE,
            jnp.sum(group_sizes > 0, dtype=jnp.int32),
        ]))
    # Whole row tiles for the kernel: the padding rows belong to no group.
    pad = -n % grouped_matmul.ROW_TILE
    xs = x[jnp.pad(order % r, (0, pad))]  # [R*k + pad, D]
    ws = jnp.pad(ws, (0, pad)).astype(x.dtype)

    # A 16-bit contraction takes no float32 precision on the chip's grouped
    # matmul, where an XLA dot takes HIGHEST as a no-op.
    grouped = grouped_matmul.for_groups(
        group_sizes, use_pallas, _PRECISION if x.dtype == jnp.float32 else None
    )
    y = _glu(
        act, grouped(xs, gate.astype(x.dtype)), lambda: grouped(xs, up.astype(x.dtype)), limit
    )
    y = grouped(y * ws[:, None], down.astype(x.dtype), jnp.float32)
    y = y[inv.reshape(k, r)]  # [k, R, D]
    y = jnp.where(on_held[..., None], y, 0.0)
    return jnp.sum(y, axis=0).astype(x.dtype)


def _top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """What ``jax.lax.top_k(x, k)`` returns, without a sort: the k largest
    entries of ``x``'s last axis in descending order, of equal ones the lower
    index first, and their int32 indices. k rounds of: the row's max, the
    first index that holds it, that one entry masked to -inf. The chip
    compiles an exact ``top_k`` as a sort of the whole axis; these are 2k
    reductions over it. For finite ``x`` (a -inf entry could tie the mask)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    vals, idx = [], []
    for _ in range(k):
        v = jnp.max(x, axis=-1, keepdims=True)
        i = jnp.min(jnp.where(x == v, iota, x.shape[-1]), axis=-1, keepdims=True)
        vals.append(v)
        idx.append(i)
        x = jnp.where(iota == i, -jnp.inf, x)
    return jnp.concatenate(vals, axis=-1), jnp.concatenate(idx, axis=-1)


def _top_groups(scores: jax.Array, k: int) -> jax.Array:
    """The set ``jax.lax.top_k(scores, k)`` picks from the last axis, as a
    bool mask and in no order: an entry is picked when fewer than k entries
    come before it, one coming before another when it is larger, or equal
    at a lower index."""
    g = scores.shape[-1]
    lower = jax.lax.broadcasted_iota(jnp.int32, (g, g), 0) < jax.lax.broadcasted_iota(
        jnp.int32, (g, g), 1
    )  # [i, j]: i < j
    si, sj = scores[..., :, None], scores[..., None, :]
    before = (si > sj) | ((si == sj) & lower)
    return jnp.sum(before, axis=-2, dtype=jnp.int32) < k


def _deepseek_moe_mlp(
    mlp: Params, cfg: LlamaConfig, x: jax.Array, stats: list | None = None,
    grouped: bool = False, use_pallas: bool = False, limits=None,
    tiles: list | None = None,
) -> jax.Array:
    """DeepSeek-V3 MoE (DeepseekV3MoE/TopkRouter): fp32 sigmoid scores;
    SELECTION adds a trained correction bias and is group-limited (experts
    partition into n_group groups, each scored by its top-2 sum, only the
    best topk_group groups stay eligible; every selection ``top_k``'s, made
    without a sort: ``_top_k``, ``_top_groups``) — the combine WEIGHTS come from
    the unbiased scores, renormalised (+1e-20) iff norm_topk_prob and
    scaled by routed_scaling_factor. A shared expert
    (n_shared_experts x the routed width) adds where the weights have one
    (MiMo-V2 has none). Two bodies for the routed experts, the caller's
    static choice: the compute-all stacked einsums of the Mixtral path (the
    default: what a caller under ``jax.vmap`` or with the expert axis sharded
    over a mesh can trace), and with ``grouped`` the rows' chosen experts
    only (``_routed_experts``; for a caller that holds its rows outside any
    ``vmap``, of whatever leading shape; ``use_pallas`` lets its grouped
    matmuls take the Pallas kernel).

    The layer routes over the router's width and computes over the experts
    it HOLDS, the stacked arrays' leading axis: with fewer held than routed
    (expert parallelism's share, ``cfg.held_experts``) it takes the held
    ids' columns of the combine weights and returns its own experts' part of
    the layer's result; what the absent experts would add is another
    process's to compute. ``stats`` (a list): gets one int32 [2] array,
    (assignments that landed on a held expert, all assignments), over every
    row of ``x``; ``tiles`` (a list): where the routed body runs, one int32
    [2] array, (the rows its grouped matmuls visit in whole row tiles, the
    held experts that got a row: ``_routed_experts``). ``limits`` (float32
    [2], the layer's row of ``layer_swiglu_limits``): the routed and the
    shared experts' SwiGLU clamps, +inf = none (``_clamp_row``)."""
    e, k = mlp["router"].shape[-1], cfg.num_experts_per_tok
    held = mlp["gate"].shape[0]
    g = cfg.moe_n_group
    with jax.named_scope("moe_router"):
        logits = jnp.einsum(
            "...ld,de->...le",
            x.astype(jnp.float32),
            mlp["router"].astype(jnp.float32),
            precision=_PRECISION,
        )  # HF routes in float32 end to end
        scores = jax.nn.sigmoid(logits)  # [..., L, E]
        choice = scores + mlp["correction_bias"].astype(jnp.float32)
        if g > 1:
            with jax.named_scope("moe_group_limit"):
                by_group = choice.reshape(*choice.shape[:-1], g, e // g)
                top2, _ = _top_k(by_group, 2)
                keep = _top_groups(top2.sum(axis=-1), cfg.moe_topk_group)  # [..., L, G]
                choice = jnp.where(jnp.repeat(keep, e // g, axis=-1), choice, 0.0)
        _, top_idx = _top_k(choice, k)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
        if cfg.moe_norm_topk_prob:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        top_w = top_w * cfg.moe_routed_scaling_factor
        ids = range(e)
        if held != e:
            ids = cfg.held_experts
            if len(ids) != held:
                raise ValueError(
                    f"expert layer holds {held} experts, the config's share is "
                    f"{len(ids)} of {e}"
                )
            if stats is not None:
                hits = (top_idx >= ids.start) & (top_idx < ids.stop)
                stats.append(
                    jnp.stack([hits.sum(), jnp.asarray(top_idx.size)]).astype(jnp.int32)
                )
    act, limits = _ACT[cfg.hidden_act], _clamp_row(cfg, limits)
    limit, shared_limit = (None, None) if limits is None else (limits[0], limits[1])
    with jax.named_scope("moe_experts"):
        if grouped:
            d = x.shape[-1]
            routed = _routed_experts(
                x.reshape(-1, d), top_idx.reshape(-1, k), top_w.reshape(-1, k),
                mlp["gate"], mlp["up"], mlp["down"], ids, act, use_pallas, limit, tiles,
            ).reshape(x.shape)
        else:
            combine = jnp.sum(
                jax.nn.one_hot(top_idx, e, dtype=jnp.float32) * top_w[..., None],
                axis=-2,
            ).astype(x.dtype)[..., ids.start : ids.stop]  # [..., L, held]
            h = _glu(
                act,
                jnp.einsum("...ld,edf->...lef", x, mlp["gate"].astype(x.dtype), precision=_PRECISION),
                lambda: jnp.einsum(
                    "...ld,edf->...lef", x, mlp["up"].astype(x.dtype), precision=_PRECISION
                ),
                limit,
            )
            c = combine[..., None]
            h = jnp.where(c != 0, h * c, jnp.zeros_like(h))
            routed = jnp.einsum(
                "...lef,efd->...ld", h, mlp["down"].astype(x.dtype), precision=_PRECISION
            )
    if "shared_gate" not in mlp:
        return routed
    with jax.named_scope("moe_shared_experts"):
        shared = _mm(
            _glu(
                act, _mm(x, mlp["shared_gate"]), lambda: _mm(x, mlp["shared_up"]),
                shared_limit,
            ),
            mlp["shared_down"],
        )
    return routed + shared


def _mlp(
    mlp: Params, x: jax.Array, cfg: LlamaConfig | None = None,
    stats: list | None = None, grouped: bool = False, use_pallas: bool = False,
    limits=None, tiles: list | None = None,
) -> jax.Array:
    if "correction_bias" in mlp:
        assert cfg is not None and cfg.num_local_experts > 0
        return _deepseek_moe_mlp(mlp, cfg, x, stats, grouped, use_pallas, limits, tiles)
    if "shared_gate" in mlp:
        assert cfg is not None and cfg.num_local_experts > 0
        return _llama4_moe_mlp(mlp, cfg, x)
    if "router" in mlp:
        assert cfg is not None and cfg.num_local_experts > 0
        return _moe_mlp(mlp, cfg, x)
    limits = _clamp_row(cfg, limits)
    return _dense_mlp(
        mlp, x, _ACT[cfg.hidden_act if cfg is not None else "silu"],
        None if limits is None else limits[1],
    )


def _gate_heads(attn: Params, cfg: LlamaConfig, o: jax.Array, h: jax.Array) -> jax.Array:
    """What MiniCPM-SALA puts between a mixer's heads and its output
    projection, where the layer's weights have it: a per-head RMSNorm of the
    heads' outputs (``o_norm``, the linear layers), then a sigmoid gate from
    the layer's normed input ``h`` (``wg``, both kinds). o: [..., L, n_q, vd];
    h: [..., L, D]. Under ``cfg.output_norm_groups`` G the norm is over G
    groups of the heads' concatenated outputs; a ``wg`` one column a head
    gates each head whole (Ling-3.0's head-wise gate)."""
    if "o_norm" in attn:
        with jax.named_scope("output_norm"):
            if cfg.output_norm_groups:
                g = cfg.output_norm_groups
                o = rms_norm(
                    o.reshape(*o.shape[:-2], g, -1), attn["o_norm"].reshape(g, -1),
                    cfg.rms_norm_eps, cfg.norm_unit_offset,
                ).reshape(o.shape)
            else:
                o = rms_norm(o, attn["o_norm"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    if "wg" in attn and attn["wg"].shape[-1] == o.shape[-2]:
        with jax.named_scope("output_gate"):
            o = o * jax.nn.sigmoid(_mm(h, attn["wg"]))[..., None]
    elif "wg" in attn:
        with jax.named_scope("output_gate"):
            o = o * jax.nn.sigmoid(_mm(h, attn["wg"])).reshape(o.shape)
    elif "wg_a" in attn:  # the gate through a narrow waist (KDA)
        with jax.named_scope("output_gate"):
            o = o * jax.nn.sigmoid(_mm(_mm(h, attn["wg_a"]), attn["wg_b"])).reshape(o.shape)
    return o


def _scaled(cfg: LlamaConfig, y: jax.Array) -> jax.Array:
    """A sublayer's output times the model's residual multiplier (muP's
    ``scale_depth / sqrt(layers)``), where it has one."""
    if cfg.residual_multiplier is None:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``m`` float32 [n, n, ...], positive: ``iters`` rounds of (every row
    over its sum + eps, then every column over its sum + eps), towards a
    doubly stochastic matrix. The matrix axes lead so that whatever trails
    (the rows of a block) lies along lanes."""
    with jax.named_scope("sinkhorn"):
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        return m


def _hc_pre(hc: Params, cfg: LlamaConfig, x: jax.Array):
    """What a sublayer reads of the ``hc_mult`` residual streams, and how its
    output goes back (mHC). x [..., n * D] -> (u [..., D], (h_post float32
    [n, R], h_res float32 [n, n, R]) for ``_hc_post``; R the rows). From the
    whole row, RMS-normed with no scale, one projection ``phi`` [n * D, 2 n +
    n * n] gives the three mixes' logits: ``h_pre = sigmoid(a_pre p + b)``,
    ``h_post = 2 sigmoid(a_post q + b)``, ``h_res = sinkhorn(exp(a_res R +
    b))``, all float32; ``u = sum_i h_pre[i] X[i]``."""
    n, f32 = cfg.hc_mult, jnp.float32
    with jax.named_scope("hc_pre"):
        rows = x.reshape(-1, x.shape[-1])
        x32 = rows.astype(f32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + cfg.hc_eps)
        logits = jnp.matmul(
            x32 * inv, hc["phi"].astype(f32), precision=_PRECISION
        ).T  # [2 n + n n, R]: the rows along lanes from here on
        a, b = hc["a"].astype(f32), hc["b"].astype(f32)[:, None]
        h_pre = jax.nn.sigmoid(a[0] * logits[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * logits[n : 2 * n] + b[n : 2 * n])
        h_res = sinkhorn(
            jnp.exp(a[2] * logits[2 * n :] + b[2 * n :]).reshape(n, n, -1),
            cfg.hc_sinkhorn_iters, cfg.hc_eps,
        )
        # Elementwise sums over the n streams (no dot: n is 4, R thousands).
        streams = x32.reshape(-1, n, x.shape[-1] // n)
        u = sum(h_pre[i][:, None] * streams[:, i] for i in range(n))
        return u.astype(x.dtype).reshape(*x.shape[:-1], -1), (h_post, h_res)


def _hc_post(cfg: LlamaConfig, x: jax.Array, y: jax.Array, mix) -> jax.Array:
    """``X'[i] = sum_j h_res[i, j] X[j] + h_post[i] y`` in float32, rounded
    once. x [..., n * D], y [..., D]."""
    h_post, h_res = mix
    n, f32 = cfg.hc_mult, jnp.float32
    with jax.named_scope("hc_post"):
        streams = x.reshape(-1, n, x.shape[-1] // n).astype(f32)
        y32 = y.reshape(-1, y.shape[-1]).astype(f32)
        out = [
            sum(h_res[i, j][:, None] * streams[:, j] for j in range(n))
            + h_post[i][:, None] * y32
            for i in range(n)
        ]
        return jnp.stack(out, axis=1).astype(x.dtype).reshape(x.shape)


def _sublayer_input(params: Params, cfg: LlamaConfig, x: jax.Array, hc: str, norm: str):
    """(h, mix): a sublayer's normed input and, for a model with ``hc_mult``
    residual streams, how its output is written back (``params[hc]``: the
    sublayer's mHC weights); ``mix`` None = the plain ``x + y``."""
    mix = None
    if cfg.hc_mult > 1:
        x, mix = _hc_pre(params[hc], cfg, x)
    return rms_norm(x, params[norm]["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset), mix


def _attn_input(params: Params, cfg: LlamaConfig, x: jax.Array):
    """``_sublayer_input`` of the attention sublayer."""
    return _sublayer_input(params, cfg, x, "hc_attn", "input_layernorm")


def _residual_attn(
    params: Params, cfg: LlamaConfig, x: jax.Array, attn_out, h=None, mix=None
) -> jax.Array:
    """Residual add of the attention sublayer. Gemma2's sandwich layout
    (``ffw_sandwich_norms``) norms the sublayer OUTPUT before the add.
    ``h``: the layer's normed input, which an output gate reads
    (``_gate_heads``). ``mix``: ``_attn_input``'s, for ``hc_mult`` streams."""
    y = _out_proj(params["attn"], _gate_heads(params["attn"], cfg, attn_out, h))
    if cfg.ffw_sandwich_norms:
        y = rms_norm(
            y,
            params["post_attention_layernorm"]["scale"],
            cfg.rms_norm_eps,
            cfg.norm_unit_offset,
        )
    if mix is not None:
        return _hc_post(cfg, x, y, mix)
    return x + _scaled(cfg, y)


def _residual_mlp(
    params: Params, cfg: LlamaConfig, x: jax.Array, stats: list | None = None,
    grouped: bool = False, use_pallas: bool = False, limits=None,
    tiles: list | None = None,
) -> jax.Array:
    """Residual add of the MLP sublayer. Standard layout norms the input
    with post_attention_layernorm; Gemma2 norms input AND output with the
    pre/post_feedforward_layernorms. Position-wise, so ``x`` may be any
    stack of rows; ``grouped`` (static): the caller holds them outside any
    ``vmap`` and a sigmoid-router expert layer may take its routed body
    (``_deepseek_moe_mlp``), with the Pallas kernel under ``use_pallas``.
    ``limits``: the layer's row of ``layer_swiglu_limits``, where the model
    has that table; ``tiles``: ``_deepseek_moe_mlp``'s."""
    pre = (
        "pre_feedforward_layernorm"
        if cfg.ffw_sandwich_norms
        else "post_attention_layernorm"
    )
    h, mix = _sublayer_input(params, cfg, x, "hc_mlp", pre)
    y = _mlp(params["mlp"], h, cfg, stats, grouped, use_pallas, limits, tiles)
    if cfg.ffw_sandwich_norms:
        y = rms_norm(
            y,
            params["post_feedforward_layernorm"]["scale"],
            cfg.rms_norm_eps,
            cfg.norm_unit_offset,
        )
    if mix is not None:
        return _hc_post(cfg, x, y, mix)
    return x + _scaled(cfg, y)


def layer_sliding_pattern(cfg: LlamaConfig) -> tuple[bool, ...]:
    """Per-layer local-attention flags, one per decoder layer: the explicit
    pattern (Gemma2/Llama4 alternation) or the uniform on/off of the
    configured local form (sliding_window / attention_chunk_size)."""
    if cfg.layer_sliding is not None:
        return cfg.layer_sliding
    local = cfg.sliding_window is not None or cfg.attention_chunk_size is not None
    return (local,) * cfg.num_hidden_layers


def _l2_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Llama4's weightless L2 norm (Llama4TextL2Norm): fp32 rsqrt-mean-square,
    cast back — applied to q/k AFTER rope on rope layers."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)).astype(x.dtype)


def position_qk(cfg: LlamaConfig, q, k, positions, sliding, rope_on, total_len=None):
    """Apply the per-layer position treatment to fresh q/k heads.

    Standard families: rope at ``positions`` (per-layer base via ``sliding``,
    gemma3). Llama4 adds: per-layer NoPE (``rope_on`` False/traced-False
    layers keep q/k un-rotated), a weightless L2 norm on q/k after rope
    (rope layers only), and temperature-tuned queries on NoPE layers
    (q *= log(floor((pos+1)/floor)+1)*coef + 1). ``rope_on`` follows the
    sliding convention: None = always on, python bool = static, traced
    scalar = selected inside the scan program. ``total_len`` (longrope
    only): real sequence length for the long/short table choice — see
    ops/rope.py rope_cos_sin.
    """
    cos, sin = rope_for_layer(cfg, positions, sliding, total_len, q.shape[-1])
    rot = apply_rope_interleaved if cfg.rope_interleaved else apply_rope
    rd = cfg.rotary_dim
    if rd is not None and rd < q.shape[-1]:
        # Partial rotary: the first rd dims of a head rotate (among
        # themselves), the rest pass through.
        q_r = jnp.concatenate([rot(q[..., :rd], cos, sin), q[..., rd:]], axis=-1)
        k_r = jnp.concatenate([rot(k[..., :rd], cos, sin), k[..., rd:]], axis=-1)
    else:
        q_r, k_r = rot(q, cos, sin), rot(k, cos, sin)
    if cfg.qk_l2_norm:
        # HF builds Llama4TextL2Norm with config.rms_norm_eps.
        q_r = _l2_norm(q_r, cfg.rms_norm_eps)
        k_r = _l2_norm(k_r, cfg.rms_norm_eps)
    if rope_on is None or rope_on is True:
        return q_r, k_r
    if cfg.attn_temperature_tuning:
        # HF Llama4: scales = log(floor((pos+1)/floor_scale)+1)*coef + 1,
        # fp32, applied to the (un-rotated) NoPE queries.
        pos = jnp.asarray(positions, jnp.float32)
        temp = (
            jnp.log(jnp.floor((pos + 1.0) / cfg.attn_floor_scale) + 1.0)
            * cfg.attn_scale_coef
            + 1.0
        )[..., None, None]
        q_n = (q.astype(jnp.float32) * temp).astype(q.dtype)
    else:
        q_n = q
    if rope_on is False:
        return q_n, k
    return (
        jnp.where(rope_on, q_r, q_n),
        jnp.where(rope_on, k_r, k),
    )


def layer_rope_pattern(cfg: LlamaConfig) -> tuple[bool, ...]:
    """Per-layer rope flags (True = rotary applied); all-on when unset."""
    if cfg.layer_rope is not None:
        return cfg.layer_rope
    return (True,) * cfg.num_hidden_layers


def rope_for_layer(
    cfg: LlamaConfig, positions: jax.Array, sliding, total_len=None, head_dim=None
):
    """cos/sin for one layer. Gemma3 gives sliding (local) layers their own
    UNSCALED rope base while full (global) layers use rope_theta +
    rope_scaling; other families have a single base. ``sliding`` follows the
    layer-fn convention: None = uniform per cfg, python bool = static
    per-layer choice, traced bool = select between the two static tables
    (both tiny) inside the scan program. ``total_len``: longrope's dynamic
    long/short selector (only the scaled global table uses it). ``head_dim``:
    the width of the heads at hand where a layer kind has its own (a
    linear-attention layer's); None = the config's."""
    dim = cfg.rotary_dim or head_dim or cfg.head_dim
    if cfg.rope_local_theta is None:
        return rope_cos_sin(
            positions, dim, cfg.rope_theta, cfg.rope_scaling_spec,
            total_len=total_len,
        )
    cos_g, sin_g = rope_cos_sin(
        positions, dim, cfg.rope_theta, cfg.rope_scaling_spec,
        total_len=total_len,
    )
    cos_l, sin_l = rope_cos_sin(positions, dim, cfg.rope_local_theta, None)
    if sliding is None:
        sliding = cfg.sliding_window is not None
    if isinstance(sliding, bool):
        return (cos_l, sin_l) if sliding else (cos_g, sin_g)
    return jnp.where(sliding, cos_l, cos_g), jnp.where(sliding, sin_l, sin_g)


def _effective_window(cfg: LlamaConfig, sliding) -> tuple[int | None, int | None, Any]:
    """Resolve (window, chunk, sliding) for one layer.

    ``sliding``: None = uniform (the cfg local form applies as-is); a python
    bool = static per-layer toggle (folds into the trace); a traced bool
    scalar = dynamic toggle (Gemma2/Llama4 layers under one scan program).
    Exactly one of window (Mistral-style band) and chunk (Llama4 chunked
    attention) can be set; both local forms share the toggle machinery.
    """
    window, chunk = cfg.sliding_window, cfg.attention_chunk_size
    if (window is None and chunk is None) or sliding is None:
        return window, chunk, None
    if isinstance(sliding, bool):
        if not sliding:
            return None, None, None
        return window, chunk, None
    return window, chunk, sliding


def _attn_kind(cfg: LlamaConfig, params: Params, sliding):
    """``layer_kind`` for any family: MLA's effective shape is one KV head
    per query head (positioned_qkv hands the kernels per-head decompressed
    K and V), whatever the config's num_key_value_heads says."""
    if cfg.kv_lora_rank:
        nh = cfg.num_attention_heads
        return (nh, nh, cfg.head_dim, cfg.v_dim), sliding
    return layer_kind(cfg, params["attn"], sliding)


def attention_plan(
    cfg: LlamaConfig, shape, sliding, lp: int, ls: int, use_pallas: bool,
    tp_mesh, has_sink: bool,
):
    """What a layer's two attention ops are dispatched with at a prefix
    bucket of ``lp`` and suffixes of ``ls`` rows: ``(flash, window, chunk,
    sliding)``. ``shape``: the layer's (heads, kv heads, qk dim, v dim);
    ``sliding``: its local toggle as ``_effective_window`` takes it.

    The flash kernels carry the full family surface — custom scale
    (query_pre_attn_scalar), softcap, sliding window / chunked masks, and
    the traced per-layer local toggle; NoPE/temperature handling lives in
    position_qk, OUTSIDE the attention op. Only shape eligibility gates
    them (tiny head dims fall back to XLA attention; ragged head dims >= 64
    like phi3's 96 pad to the lane multiple inside the kernels). Under
    tensor parallelism (``tp_mesh``) the kernels run per head-shard via
    shard_map, so eligibility is checked on PER-SHARD head counts; the
    head-sharded kernels carry no sink yet (XLA op). Static facts only: the
    traced layer functions and the host's count of the kernels' steps
    (``runtime/executor._flash_steps``) read the same plan."""
    n_q, n_kv, hd, vd = shape
    window, chunk, sliding = _effective_window(cfg, sliding)
    if (window is not None and lp + ls <= window) or (
        chunk is not None and lp + ls <= chunk
    ):
        # Max query-key distance at these (static) bucket shapes is
        # lp + ls - 1 < window (or every position sits in chunk 0): the
        # local mask equals full causal, so drop it — keeping the flash
        # kernels eligible (the common case for Mistral's 4096 window and
        # Llama4's 8192 chunks under the 4096 token cap).
        window = chunk = sliding = None
    tp_size = tp_mesh.shape["tp"] if tp_mesh is not None else 1
    flash = (
        use_pallas
        and pallas_attention.supports(
            n_q // tp_size, n_kv // tp_size, hd, ls, lp, v_dim=vd
        )
        and not (has_sink and tp_mesh is not None)
    )
    return flash, window, chunk, sliding


def layer_attention_plan(
    cfg: LlamaConfig, layer: int, lp: int, ls: int, use_pallas: bool, tp_mesh=None
):
    """``attention_plan`` of decoder layer ``layer`` from the config alone,
    for a host that counts what the layer's kernels do and holds no weights:
    ``(flash, shape, window, chunk, local_on)``, or None for a
    linear-attention layer. ``local_on`` is the python value of the layer's
    local toggle as the kernels see it: a program that carries the toggle
    traced (one scan over layers of both kinds and ONE shape: the executor
    ships the pattern's flags with the segment) keeps the static window and
    is told per layer; elsewhere the toggle folds into ``window``/``chunk``."""
    if cfg.layer_linear is not None and cfg.layer_linear[layer]:
        return None
    local = layer_sliding_pattern(cfg)[layer]
    if cfg.kv_lora_rank:
        nh = cfg.num_attention_heads
        shape, by_shape = (nh, nh, cfg.head_dim, cfg.v_dim), False
    else:
        shape = cfg.attn_shape(local)
        by_shape = cfg.attn_shape(False) != cfg.attn_shape(True)
    traced = cfg.layer_sliding is not None and not by_shape
    # np.bool_ stands for the traced flag: no python bool, so
    # _effective_window keeps the static window and hands the toggle back.
    flash, window, chunk, sliding = attention_plan(
        cfg, shape, np.bool_(local) if traced else local, lp, ls, use_pallas,
        tp_mesh, cfg.attn_sink_local if local else cfg.attn_sink_global,
    )
    return flash, shape, window, chunk, sliding is None or bool(sliding)


def _attention_scope(cfg: LlamaConfig, sliding):
    """The layer's ``attention`` scope, with the layer's kind inside it
    (``attention_window`` / ``attention_full``) where the kind is static: a
    traced per-layer flag (one scan over both kinds) has no one name."""
    if sliding is None:
        local = cfg.sliding_window is not None or cfg.attention_chunk_size is not None
    elif isinstance(sliding, bool):
        local = sliding
    else:
        return jax.named_scope("attention")
    stack = contextlib.ExitStack()
    stack.enter_context(jax.named_scope("attention"))
    stack.enter_context(
        jax.named_scope("attention_window" if local else "attention_full")
    )
    return stack


def _linear_attention_scope():
    """``linear_attention`` inside the layer's ``attention`` scope."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.named_scope("attention"))
    stack.enter_context(jax.named_scope("linear_attention"))
    return stack


def _moe_counts(stats: list) -> jax.Array:
    """Sum of a layer's ``_deepseek_moe_mlp`` stats (or tiles): int32 [2],
    zeros for a layer that routed nothing over held experts."""
    return sum(stats, jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def embed(
    params: Params, ids: jax.Array, dtype: jnp.dtype, cfg: LlamaConfig | None = None
) -> jax.Array:
    """Token ids [..., L] -> hidden states [..., L, D].

    Gemma (``cfg.embed_scale``) multiplies by sqrt(hidden_size), with the
    normalizer itself rounded to the compute dtype first (HF PR #29402 —
    sqrt(3072) becomes 55.5 in fp16, reproduced for parity)."""
    x = params["embedding"].astype(dtype)[ids]
    if cfg is not None and cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
    if cfg is not None and cfg.embed_multiplier is not None:
        x = x * jnp.asarray(cfg.embed_multiplier, dtype)  # MiniCPM's scale_emb
    if cfg is not None and cfg.hc_mult > 1:  # every residual stream starts as e
        x = jnp.tile(x, cfg.hc_mult)
    return x


def decoder_layer(
    params: Params,
    cfg: LlamaConfig,
    x: jax.Array,
    positions: jax.Array,
    mask: jax.Array | None,
    sliding=None,
    rope_on=None,
    total_len=None,
    log_decay=None,
    limits=None,
) -> jax.Array:
    """Plain decoder layer. x: [..., L, D]; positions int [..., L] or [L];
    mask broadcastable to [..., L, L] (caller bakes any local mask in;
    ``sliding``/``rope_on`` select the per-layer rope base / NoPE;
    ``total_len`` is longrope's real-length selector). A linear-attention
    layer (``is_linear``) takes ``log_decay`` [heads] and no mask: it is
    causal over x's L rows (x: [B, L, D]). ``limits``: the layer's row of
    ``layer_swiglu_limits``."""
    h, mix = _attn_input(params, cfg, x)
    if is_linear(cfg, params["attn"]) and cfg.linear_kind == "kda":
        attn_out, _, _ = _kda_mixer(params["attn"], cfg, h, None, None, None, False)
        x = _residual_attn(params, cfg, x, attn_out, h, mix)
        return _residual_mlp(params, cfg, x, limits=limits)
    q, k, v = positioned_qkv(params, cfg, h, positions, sliding, rope_on, total_len)
    if is_linear(cfg, params["attn"]):
        with _linear_attention_scope():
            rows = jnp.broadcast_to(log_decay, (x.shape[-2], log_decay.shape[-1]))
            attn_out, _ = lightning_attention.lightning_attention_xla(q, k, v, rows)
    else:
        attn_out = attention(
            q, k, v, mask, scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
            sink=params["attn"].get("sink"),
        )
    x = _residual_attn(params, cfg, x, attn_out, h, mix)
    return _residual_mlp(params, cfg, x, limits=limits)


def _flash_tp_causal(mesh, q, k, v, plen, local_on, kw):
    """flash_causal_attention under tensor parallelism: shard_map over the
    (embarrassingly parallel) heads axis — pallas_call has no GSPMD
    partitioning rule, so the kernel runs per-shard on each chip's head
    slice. GQA ratios survive the split (both head counts divide by tp)."""
    from jax.sharding import PartitionSpec as P

    flag = jnp.asarray(True if local_on is None else local_on)
    h = P(None, "tp", None)
    f = lambda q, k, v, plen, flag: pallas_attention.flash_causal_attention(
        q, k, v, plen, local_on=flag, **kw
    )
    return jax.shard_map(
        f, mesh=mesh, in_specs=(h, h, h, P(), P()), out_specs=h,
        check_vma=False,
    )(q, k, v, plen, flag)


def _flash_tp_prefix_shared(mesh, qs, kp, vp, ks, vs, plen, local_on, kw):
    """flash_prefix_shared_attention under tensor parallelism (see
    ``_flash_tp_causal``)."""
    from jax.sharding import PartitionSpec as P

    flag = jnp.asarray(True if local_on is None else local_on)
    hq = P(None, None, "tp", None)  # [S, Ls, heads, hd]
    hp = P(None, "tp", None)  # [Lp, kv_heads, hd]
    f = lambda qs, kp, vp, ks, vs, plen, flag: (
        pallas_attention.flash_prefix_shared_attention(
            qs, kp, vp, ks, vs, plen, local_on=flag, **kw
        )
    )
    return jax.shard_map(
        f, mesh=mesh, in_specs=(hq, hp, hp, hq, hq, P(), P()), out_specs=hq,
        check_vma=False,
    )(qs, kp, vp, ks, vs, plen, flag)


def _flash_tp_decode(mesh, q, kp, vp, ks, vs, kg, vg, plen, eos, t, local_on, kw):
    """flash_decode_attention under tensor parallelism (see
    ``_flash_tp_causal``): heads are embarrassingly parallel, so the kernel
    runs per head-shard inside a shard_map; replicated KV inputs reshard to
    the head split at entry."""
    from jax.sharding import PartitionSpec as P

    flag = jnp.asarray(True if local_on is None else local_on)
    hq = P(None, None, "tp", None)  # [S, 1, heads, hd]
    hp = P(None, "tp", None)  # [Lp, kv_heads, hd]
    hs = P(None, None, "tp", None)  # [S, L, kv_heads, hd]
    f = lambda q, kp, vp, ks, vs, kg, vg, plen, eos, t, flag: (
        pallas_attention.flash_decode_attention(
            q, kp, vp, ks, vs, kg, vg, plen, eos, t, local_on=flag, **kw
        )
    )
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(hq, hp, hp, hs, hs, hs, hs, P(), P(), P(), P()),
        out_specs=hq,
        check_vma=False,
    )(q, kp, vp, ks, vs, kg, vg, plen, eos, t, flag)


def linear_uses_kernel(cfg: LlamaConfig, lp: int, ls: int, use_pallas: bool, tp_mesh=None) -> bool:
    """Whether a linear-attention layer over a (prefix bucket ``lp``, suffix
    bucket ``ls``) prompt runs the Pallas kernel or the XLA op: one choice for
    both of its calls, from the shapes (the sweep's record counts by it)."""
    _, _, d, vd = cfg.attn_shape(linear=True)
    op = kda_attention if cfg.linear_kind == "kda" else lightning_attention
    return (
        use_pallas and tp_mesh is None
        and op.supports(d, vd, lp) and op.supports(d, vd, ls)
    )


def _causal_conv(x: jax.Array, taps: jax.Array, tail: jax.Array | None) -> jax.Array:
    """Causal depthwise convolution over the rows: ``y_t = sum_j taps[j] *
    x_{t - (K - 1) + j}`` (the last tap on the row itself), float32. x
    [..., L, C]; taps [K, C]; tail [..., K - 1, C]: the rows before x's first
    (None = zeros: a sequence's start)."""
    kk = taps.shape[0]
    x32 = x.astype(jnp.float32)
    if tail is None:
        tail = jnp.zeros((*x.shape[:-2], kk - 1, x.shape[-1]), jnp.float32)
    padded = jnp.concatenate([tail.astype(jnp.float32), x32], axis=-2)
    length = x.shape[-2]
    w = taps.astype(jnp.float32)
    return sum(
        w[j] * jax.lax.slice_in_dim(padded, j, j + length, axis=-2) for j in range(kk)
    )


def _kda_mixer(attn: Params, cfg: LlamaConfig, h: jax.Array, live, tail, state, kernel: bool):
    """A KDA layer's heads over rows ``h`` [N, L, D] (normed input): q, k, v
    projections through a causal short convolution and SiLU, q and k
    L2-normalised a head (q over sqrt(d) besides), a log-decay ``g =
    gate_lower_bound * sigmoid(exp(A_log) * (W_f2 W_f1 h + dt_bias))`` (or
    ``W_f h`` at full rank: ``wf``) a key channel and ``beta = sigmoid(W_b h)`` a head, then the delta rule
    (``ops/kda_attention.py``). ``live`` bool [L] (None = all): rows past a
    right-padded sequence's end get ``g = 0``, ``beta = 0`` and leave the
    state alone. ``tail`` [N, K - 1, 3 H d]: the pre-convolution rows before
    the first (None = a sequence's start); ``state`` float32 [H, d, dv] the N
    sequences start from (None = zeros). -> (o [N, L, H, dv], the
    pre-convolution rows [N, L, 3 H d], the final states [N, H, d, dv])."""
    nh, _, d, dv = cfg.attn_shape(linear=True)
    f32 = jnp.float32
    lead = h.shape[:-1]
    with jax.named_scope("kda_conv"):
        pre = jnp.concatenate([_mm(h, attn[w]) for w in ("wq", "wk", "wv")], axis=-1)
        taps = jnp.concatenate([attn[w] for w in ("conv_q", "conv_k", "conv_v")], axis=-1)
        qkv = jax.nn.silu(_causal_conv(pre, taps, tail))
        q, k, v = jnp.split(qkv, [nh * d, 2 * nh * d], axis=-1)
        q, k = q.reshape(*lead, nh, d), k.reshape(*lead, nh, d)
        unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q, k = unit(q) * d**-0.5, unit(k)
    with jax.named_scope("kda_gate"):
        if "wf" in attn:  # at full rank (Ling-3.0)
            f = _mm(h, attn["wf"]).astype(f32) + attn["dt_bias"].astype(f32)
        else:
            f = _mm(_mm(h, attn["f_a"]), attn["f_b"]).astype(f32) + attn["dt_bias"].astype(f32)
        rate = jnp.exp(attn["A_log"].astype(f32))[:, None]
        g = cfg.linear_gate_lower_bound * jax.nn.sigmoid(rate * f.reshape(*lead, nh, d))
        beta = jax.nn.sigmoid(_mm(h, attn["wb"]).astype(f32))  # [N, L, H]
        if live is not None:
            g = jnp.where(live[:, None, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
    op = kda_attention.kda_attention if kernel else kda_attention.kda_attention_xla
    with _linear_attention_scope(), jax.named_scope("kda_attention"):
        o, final = op(
            q.astype(h.dtype), k.astype(h.dtype), v.reshape(*lead, nh, dv).astype(h.dtype),
            g, beta, state,
        )
    return o, pre, final


def _kda_prefix_suffix(params, cfg, prefix_h, suffix_h, prefix_len, kernel: bool):
    """``_linear_prefix_suffix`` for a KDA layer. The prefix hands its
    suffixes TWO things at the dynamic ``prefix_len`` inside its right-padded
    bucket: the state (rows past ``prefix_len`` neither decay it nor write to
    it, so the bucket's last is the one at ``prefix_len``) and the last
    ``linear_conv_size - 1`` pre-convolution rows of q, k and v before
    ``prefix_len`` (zeros where the prefix is shorter than that)."""
    lp, kk = prefix_h.shape[0], cfg.linear_conv_size
    h, mix = _attn_input(params, cfg, prefix_h)
    live = jnp.arange(lp) < prefix_len
    o, pre, state = _kda_mixer(params["attn"], cfg, h[None], live, None, None, kernel)
    prefix_out = _residual_attn(params, cfg, prefix_h, o[0], h, mix)
    # Row prefix_len - (K - 1) + j of the prefix is row prefix_len + j of the
    # prefix with K - 1 rows of zeros before it.
    tail = jax.lax.dynamic_slice_in_dim(
        jnp.pad(pre[0], ((kk - 1, 0), (0, 0))), prefix_len, kk - 1, axis=0
    )
    hs, mix = _attn_input(params, cfg, suffix_h)
    tails = jnp.broadcast_to(tail, (suffix_h.shape[0], *tail.shape))
    os_, _, _ = _kda_mixer(params["attn"], cfg, hs, None, tails, state[0], kernel)
    return prefix_out, _residual_attn(params, cfg, suffix_h, os_, hs, mix)


def _linear_prefix_suffix(
    params, cfg, prefix_h, suffix_h, prefix_len, log_decay, kernel: bool, rope_on, total_len,
):
    """The attention half of ``prefix_suffix_layer`` for a linear-attention
    layer: (prefix, suffixes) residual streams as they enter the MLP half."""
    if cfg.linear_kind == "kda":
        return _kda_prefix_suffix(params, cfg, prefix_h, suffix_h, prefix_len, kernel)
    lp, ls = prefix_h.shape[0], suffix_h.shape[1]
    eps = cfg.rms_norm_eps
    op = (
        lightning_attention.lightning_attention if kernel
        else lightning_attention.lightning_attention_xla
    )
    h = rms_norm(prefix_h, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    q, k, v = positioned_qkv(params, cfg, h, jnp.arange(lp), None, rope_on, total_len)
    # Rows past the real prefix stop the clock: no decay, nothing added.
    live = jnp.arange(lp) < prefix_len
    k = jnp.where(live[:, None, None], k, jnp.zeros_like(k))
    rows = jnp.where(live[:, None], log_decay[None, :], 0.0)
    with _linear_attention_scope():
        o, state = op(q[None], k[None], v[None], rows)
    prefix_out = _residual_attn(params, cfg, prefix_h, o[0], h)

    hs = rms_norm(suffix_h, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    qs, ks, vs = positioned_qkv(
        params, cfg, hs, prefix_len + jnp.arange(ls), None, rope_on, total_len
    )
    with _linear_attention_scope():
        os_, _ = op(qs, ks, vs, jnp.broadcast_to(log_decay, (ls, log_decay.shape[-1])), state[0])
    return prefix_out, _residual_attn(params, cfg, suffix_h, os_, hs)


def prefix_suffix_layer(
    params: Params,
    cfg: LlamaConfig,
    prefix_h: jax.Array,
    suffix_h: jax.Array,
    prefix_len: jax.Array,
    use_pallas: bool = False,
    return_kv: bool = False,
    sliding=None,
    rope_on=None,
    tp_mesh=None,
    total_len=None,
    moe_stats: bool = False,
    attn_only: bool = False,
    log_decay=None,
) -> tuple[jax.Array, ...]:
    """One decoder layer over a (prefix, suffixes) prompt — the streaming hot op.

    prefix_h: [Lp, D] right-padded to the Lp bucket; only the first
        ``prefix_len`` positions are real.
    suffix_h: [S, Ls, D], right-padded suffix continuations.
    prefix_len: int32 scalar (dynamic value; shapes stay static).
    total_len: longrope only — the prompt's real total length (prefix +
        longest suffix), an int32 scalar selecting the long/short table
        for BOTH the shared prefix KV and the suffixes. The executor
        rejects prompts whose suffixes straddle the original_max boundary
        (mixed regimes would need the shared prefix KV rotated per
        suffix, defeating the prefix-sharing trick).

    Semantics match the reference exactly (``/root/reference/utils.py:270-279``):
    the prefix runs a causal self-attention once and its (post-RoPE) KV is
    shared across all S suffixes; each suffix token attends to every real
    prefix position plus causally within its own suffix, at rotary positions
    ``prefix_len + i``.

    ``use_pallas`` (static) swaps both attention ops for the Pallas flash
    kernels (ops/pallas_attention.py) when the shapes are eligible — same
    semantics, no [Lq, Lk] score materialisation.

    ``moe_stats`` (static): one more output at the end, int32 [2]: the
    layer's (assignments on held experts, all assignments) where the expert
    layer holds a share of its experts, zeros elsewhere.

    ``attn_only`` (static): stop after the attention half and return the
    residual streams as they enter the MLP half, for a caller that runs
    that half (``_residual_mlp``, position-wise) over many prompts' rows at
    once.

    ``log_decay`` (float32 [heads]): the layer's per-head log-decay where it
    is a linear-attention layer (``is_linear``; ``layer_log_decay``). Such a
    layer shares a STATE where a softmax layer shares keys and values: the
    prefix runs the decayed recurrence from a zero state, its rows at
    ``t >= prefix_len`` neither decaying the state nor adding to it, so the
    state at the bucket's end is the state at ``prefix_len``; each suffix
    continues from that one state at positions ``prefix_len + i``. The state
    (float32 [heads, qk dim, v dim]) lives inside this call.
    """
    lp, _ = prefix_h.shape
    s, ls, _ = suffix_h.shape
    stats = [] if moe_stats else None
    if return_kv:
        cfg.require_one_attention_shape("a KV cache (return_kv)", layer_fn=True)
        cfg.require_single_visit("a KV cache (return_kv)")
    if is_linear(cfg, params["attn"]):
        prefix_out, suffix_out = _linear_prefix_suffix(
            params, cfg, prefix_h, suffix_h, prefix_len, log_decay,
            linear_uses_kernel(cfg, lp, ls, use_pallas, tp_mesh), rope_on, total_len,
        )
        if not attn_only:
            prefix_out = _residual_mlp(params, cfg, prefix_out, stats)
            suffix_out = _residual_mlp(params, cfg, suffix_out, stats)
        out = (prefix_out, suffix_out)
        return out + (_moe_counts(stats),) if moe_stats else out
    (n_q, n_kv, hd, vd), sliding = _attn_kind(cfg, params, sliding)
    sink = params["attn"].get("sink")
    rope_sliding = sliding  # rope base and scope survive the window shortcut
    # MLA (kv_lora_rank) rides the flash path too: the scoring kernels
    # carry q/k's head dim and V's own dim independently (QK^T over
    # head_dim, PV over v_dim) — positioned_qkv hands them per-head
    # decompressed K (nope + shared rope key) and V, so the EFFECTIVE kv
    # head count is the attention head count (GQA ratio 1: _attn_kind).
    flash, window, chunk, sliding = attention_plan(
        cfg, (n_q, n_kv, hd, vd), sliding, lp, ls, use_pallas, tp_mesh,
        sink is not None,
    )

    # --- prefix: causal self-attention, keep post-RoPE KV ---
    h, mix = _attn_input(params, cfg, prefix_h)
    q, k, v = positioned_qkv(
        params, cfg, h, jnp.arange(lp), rope_sliding, rope_on, total_len
    )
    if flash:
        # Rows at i >= prefix_len are padding; the kernel's valid-len mask
        # additionally skips fully-masked KV blocks.
        flash_kw = dict(
            scale=cfg.attn_scale,
            window=window,
            chunk=chunk,
            softcap=cfg.attn_logit_softcap,
        )
    with _attention_scope(cfg, rope_sliding):
        if flash and tp_mesh is not None:
            attn_out = _flash_tp_causal(
                tp_mesh, q, k, v, prefix_len, sliding, flash_kw
            )
        elif flash:
            attn_out = pallas_attention.flash_causal_attention(
                q, k, v, prefix_len, local_on=sliding, sink=sink, **flash_kw
            )
        else:
            if sliding is None:
                mask = causal_mask(lp, lp, window=window, chunk=chunk)
            else:  # traced per-layer toggle: local mask iff this layer is local
                mask = jnp.where(
                    sliding,
                    causal_mask(lp, lp, window=window, chunk=chunk),
                    causal_mask(lp, lp),
                )
            attn_out = attention(
                q, k, v, mask, scale=cfg.attn_scale,
                softcap=cfg.attn_logit_softcap, sink=sink,
            )
    prefix_out = _residual_attn(params, cfg, prefix_h, attn_out, h, mix)
    if not attn_only:
        prefix_out = _residual_mlp(params, cfg, prefix_out, stats)

    # --- suffixes: batched attention over [shared prefix KV ; own causal KV],
    # prefix KV never expanded across suffixes (ops.prefix_shared_attention) ---
    hs, mix = _attn_input(params, cfg, suffix_h)
    pos_s = prefix_len + jnp.arange(ls)
    qs, ks, vs = positioned_qkv(
        params, cfg, hs, pos_s, rope_sliding, rope_on, total_len
    )

    with _attention_scope(cfg, rope_sliding):
        if flash and tp_mesh is not None:
            attn_s = _flash_tp_prefix_shared(
                tp_mesh, qs, k, v, ks, vs, prefix_len, sliding, flash_kw
            )
        elif flash:
            attn_s = pallas_attention.flash_prefix_shared_attention(
                qs, k, v, ks, vs, prefix_len, local_on=sliding, sink=sink,
                **flash_kw,
            )
        else:
            attn_s = prefix_shared_attention(
                qs,
                k,
                v,
                ks,
                vs,
                prefix_len,
                scale=cfg.attn_scale,
                window=window,
                softcap=cfg.attn_logit_softcap,
                sliding=sliding,
                chunk=chunk,
                sink=sink,
            )
    suffix_out = _residual_attn(params, cfg, suffix_h, attn_s, hs, mix)
    if not attn_only:
        suffix_out = _residual_mlp(params, cfg, suffix_out, stats)
    out = (prefix_out, suffix_out)
    if return_kv:
        # Post-RoPE KV, reusable across decode steps (runtime/decode.py).
        out += ({"kp": k, "vp": v, "ks": ks, "vs": vs},)
    if moe_stats:
        out += (_moe_counts(stats),)
    return out


def suffix_only_layer(
    params: Params,
    cfg: LlamaConfig,
    kp: jax.Array,
    vp: jax.Array,
    suffix_h: jax.Array,
    prefix_len: jax.Array,
    use_pallas: bool = False,
    sliding=None,
    rope_on=None,
    tp_mesh=None,
    total_len=None,
) -> tuple[jax.Array, dict]:
    """The suffix half of :func:`prefix_suffix_layer`, fed a CACHED prefix KV.

    In ``prefix_suffix_layer`` the suffix stream depends on the prefix only
    through the post-RoPE (k, v) — so when a pooled prefix entry
    (runtime/kvpool.py) already holds those arrays, a same-prefix wave can
    skip the prefix stream entirely and run just this half, bit-identically:
    same norm, same rotary positions ``prefix_len + i``, same shared-prefix
    attention ops, same residual MLP.

    kp/vp: ``[Lp, n_kv, hd]`` / ``[Lp, n_kv, v_dim]`` post-RoPE prefix KV at
        the SAME Lp bucket the entry was prefilled at (positions past
        ``prefix_len`` are the pad tail, masked like always).
    Returns ``(suffix_out, {"ks": ks, "vs": vs})`` — the caller re-attaches
    kp/vp to rebuild the full decode-KV dict.
    """
    cfg.require_one_attention_shape("a cached prefix KV (suffix_only_layer)", layer_fn=True)
    cfg.require_single_visit("a cached prefix KV (suffix_only_layer)")
    lp = kp.shape[0]
    s, ls, _ = suffix_h.shape
    eps = cfg.rms_norm_eps
    (n_q, n_kv, hd, vd), sliding = _attn_kind(cfg, params, sliding)
    sink = params["attn"].get("sink")
    rope_sliding = sliding  # rope base and scope survive the window shortcut
    # The same plan as prefix_suffix_layer's, from the same bucket shapes.
    flash, window, chunk, sliding = attention_plan(
        cfg, (n_q, n_kv, hd, vd), sliding, lp, ls, use_pallas, tp_mesh,
        sink is not None,
    )

    hs = rms_norm(suffix_h, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    pos_s = prefix_len + jnp.arange(ls)
    qs, ks, vs = positioned_qkv(
        params, cfg, hs, pos_s, rope_sliding, rope_on, total_len
    )

    with _attention_scope(cfg, rope_sliding):
        if flash:
            flash_kw = dict(
                scale=cfg.attn_scale,
                window=window,
                chunk=chunk,
                softcap=cfg.attn_logit_softcap,
            )
            if tp_mesh is not None:
                attn_s = _flash_tp_prefix_shared(
                    tp_mesh, qs, kp, vp, ks, vs, prefix_len, sliding, flash_kw
                )
            else:
                attn_s = pallas_attention.flash_prefix_shared_attention(
                    qs, kp, vp, ks, vs, prefix_len, local_on=sliding,
                    sink=sink, **flash_kw,
                )
        else:
            attn_s = prefix_shared_attention(
                qs,
                kp,
                vp,
                ks,
                vs,
                prefix_len,
                scale=cfg.attn_scale,
                window=window,
                softcap=cfg.attn_logit_softcap,
                sliding=sliding,
                chunk=chunk,
                sink=sink,
            )
    suffix_mid = _residual_attn(params, cfg, suffix_h, attn_s, hs)
    suffix_out = _residual_mlp(params, cfg, suffix_mid)
    return suffix_out, {"ks": ks, "vs": vs}


def decode_step_layer(
    params: Params,
    cfg: LlamaConfig,
    x: jax.Array,
    kv: Params,
    prefix_len: jax.Array,
    suffix_eos: jax.Array,
    t: jax.Array,
    sliding=None,
    rope_on=None,
    use_pallas: bool = False,
    tp_mesh=None,
) -> tuple[jax.Array, Params]:
    """One decoder layer for the K NEWEST tokens per suffix, against cached KV.

    The KV-cache decode path (no reference equivalent — its generation loop
    re-streams the full prompt per token, SURVEY.md §3.5). x: [S, K, D]
    (K=1 for plain decode, K=draft+1 for the speculative verify step);
    kv: {'kp','vp' [Lp,n_kv,hd], 'ks','vs' [S,Ls,n_kv,hd],
    'kg','vg' [S,T,n_kv,hd]} with generated-token slots < t filled;
    t: int32 scalar or per-suffix [S] vector — the fed tokens take slots
    ``t..t+K-1`` and rotary positions ``prefix_len + (suffix_eos[s]+1) +
    t(+j)``. Returns (x_out, kv with those slots of kg/vg written).
    ``use_pallas`` (static) swaps the attention for the flash decode kernel
    when eligible (single-token, shared slot) — unlike the XLA op it skips
    prefix-KV blocks past the real prefix length. Under tensor parallelism
    (``tp_mesh``) the kernel runs per head-shard via shard_map.
    """
    cfg.require_one_attention_shape("KV-cache decoding (decode_step_layer)", layer_fn=True)
    cfg.require_single_visit("KV-cache decoding (decode_step_layer)")
    eps = cfg.rms_norm_eps
    (n_q, n_kv, hd, vd), sliding = _attn_kind(cfg, params, sliding)
    sink = params["attn"].get("sink")
    rope_sliding = sliding
    kq = x.shape[1]
    base = jnp.asarray(t, jnp.int32)
    h = rms_norm(x, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    pos = (
        prefix_len + suffix_eos + 1 + jnp.broadcast_to(base, suffix_eos.shape)
    )[:, None] + jnp.arange(kq)[None, :]  # [S, K]
    # longrope's per-suffix real length at this step (the fed tokens'
    # last position + 1). DecodeGenerator rejects generations that CROSS
    # the original_max boundary (parked KV would need re-rotation), so
    # within one generation this always lands on one side.
    total_len = pos[:, -1] + 1 if cfg.rope_scaling_kind == "longrope" else None
    q, k_new, v_new = positioned_qkv(
        params, cfg, h, pos, rope_sliding, rope_on, total_len
    )  # [S, K, n, hd]

    kv = dict(kv)
    if base.ndim == 0:
        kv["kg"] = jax.lax.dynamic_update_slice_in_dim(kv["kg"], k_new, base, axis=1)
        kv["vg"] = jax.lax.dynamic_update_slice_in_dim(kv["vg"], v_new, base, axis=1)
    else:
        # Speculative passes: each suffix writes its K slots at its OWN
        # offset (suffixes accept different draft counts, so their slot
        # clocks drift apart).
        upd = jax.vmap(
            lambda buf, new, off: jax.lax.dynamic_update_slice_in_dim(
                buf, new, off, axis=0
            )
        )
        kv["kg"] = upd(kv["kg"], k_new, base)
        kv["vg"] = upd(kv["vg"], v_new, base)

    window, chunk, sliding = _effective_window(cfg, sliding)
    tp_size = tp_mesh.shape["tp"] if tp_mesh is not None else 1
    with _attention_scope(cfg, rope_sliding):
        if (
            use_pallas and not cfg.kv_lora_rank and kq == 1 and base.ndim == 0
            and not (sink is not None and tp_mesh is not None)
            and pallas_attention.supports_decode(
                n_q // tp_size, n_kv // tp_size, hd, v_dim=vd
            )
        ):
            flash_kw = dict(
                scale=cfg.attn_scale,
                window=window,
                softcap=cfg.attn_logit_softcap,
                chunk=chunk,
            )
            if tp_mesh is not None:
                attn_out = _flash_tp_decode(
                    tp_mesh, q, kv["kp"], kv["vp"], kv["ks"], kv["vs"],
                    kv["kg"], kv["vg"], prefix_len, suffix_eos, t, sliding,
                    flash_kw,
                )
            else:
                attn_out = pallas_attention.flash_decode_attention(
                    q,
                    kv["kp"],
                    kv["vp"],
                    kv["ks"],
                    kv["vs"],
                    kv["kg"],
                    kv["vg"],
                    prefix_len,
                    suffix_eos,
                    t,
                    local_on=sliding,
                    sink=sink,
                    **flash_kw,
                )
        else:
            attn_out = decode_attention(
                q,
                kv["kp"],
                kv["vp"],
                kv["ks"],
                kv["vs"],
                kv["kg"],
                kv["vg"],
                prefix_len,
                suffix_eos,
                t,
                scale=cfg.attn_scale,
                window=window,
                softcap=cfg.attn_logit_softcap,
                sliding=sliding,
                chunk=chunk,
                sink=sink,
            )
    mid = _residual_attn(params, cfg, x, attn_out, h)
    return _residual_mlp(params, cfg, mid), kv


def select_eos_and_norm(
    params: Params, cfg: LlamaConfig, suffix_h: jax.Array, suffix_eos: jax.Array
) -> jax.Array:
    """The reference's ``model.norm`` stage (``/root/reference/utils.py:281-286``):
    keep only the last real token of each suffix, then RMSNorm.

    suffix_h: [S, Ls, D]; suffix_eos: int [S] (index of last non-pad token).
    Returns [S, 1, D].
    """
    last = jnp.take_along_axis(suffix_h, suffix_eos[:, None, None], axis=1)
    return final_norm(params, cfg, last)


def final_norm(params: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    """``model.norm`` and, where the model has one, muP's logit divisor
    (``hidden_size / dim_model_base``) on what the head reads. A model with
    ``hc_mult`` residual streams norms their sum."""
    if cfg.hc_mult > 1:
        streams = x.reshape(*x.shape[:-1], cfg.hc_mult, -1).astype(jnp.float32)
        x = jnp.sum(streams, axis=-2).astype(x.dtype)
    x = rms_norm(x, params["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    if cfg.logit_divisor is not None:
        x = x / jnp.asarray(cfg.logit_divisor, x.dtype)
    return x


# ---------------------------------------------------------------------------
# A looped stack's step end (Ouro): the exit gate and the rule that picks
# each scored token's step. The final norm itself is ``final_norm``, over
# every row between steps and over the scored rows at the last.
# ---------------------------------------------------------------------------

def exit_gate(params: Params, h: jax.Array) -> jax.Array:
    """The probability of stopping at this step, one a row: ``sigmoid(w_g .
    h + b_g)`` in float32, with h [..., D] the step's NORMED output and the
    gate's leaves beside the final norm's scale (``params["gate"]``: kernel
    [D, 1], bias [1])."""
    with jax.named_scope("exit_gate"):
        g = params["gate"]
        z = _mm(h, g["kernel"]).astype(jnp.float32)[..., 0]
        return jax.nn.sigmoid(z + g["bias"].astype(jnp.float32)[0])


def exit_init(shape: tuple[int, ...], d: int, dtype) -> tuple:
    """The exit state of ``shape`` scored rows before the first step:
    ``(remaining, cum, expected, chosen)`` = (the probability of not having
    stopped yet, 1; the cumulative exit probability, 0; the running sum of
    step x probability, 0; the hidden state [*shape, 1, D] of the step a row
    has been given, none yet)."""
    return (
        jnp.ones(shape, jnp.float32),
        jnp.zeros(shape, jnp.float32),
        jnp.zeros(shape, jnp.float32),
        jnp.zeros((*shape, 1, d), dtype),
    )


def exit_step(
    cfg: LlamaConfig, params: Params, state: tuple, h: jax.Array, step, last: bool
) -> tuple:
    """One step's end for the scored rows. h [..., 1, D]: their normed
    output at step ``step`` (1-based, may be traced). With lambda the gate's
    probability, step t < T stops with p_t = lambda_t * prod_{j<t}(1 -
    lambda_j) and the last step takes what is left, p_T = prod_{j<T}(1 -
    lambda_j). A row is given the FIRST step whose cumulative probability
    reaches ``early_exit_threshold`` (the last step's always does). Returns
    the new state; at a threshold >= 1 the caller reads the last step's h
    and ``chosen`` stays unread."""
    remaining, cum, expected, chosen = state
    p = remaining if last else exit_gate(params, h[..., 0, :]) * remaining
    q = jnp.float32(cfg.early_exit_threshold)
    new_cum = cum + p
    reached = jnp.ones_like(cum, bool) if last else new_cum >= q
    given = jnp.logical_and(cum < q, reached)
    return (
        remaining - p,
        new_cum,
        expected + jnp.asarray(step, jnp.float32) * p,
        jnp.where(given[..., None, None], h, chosen),
    )


def lm_head_scores_multi(
    params: Params, h: jax.Array, softcap: float | None = None
) -> jax.Array:
    """Next-token distributions for EVERY position: h [..., K, D] -> float32
    scores [..., K, V]. The speculative verify step's head (lm_head_scores
    keeps only position 0); same softcap-then-softmax semantics."""
    logits = _mm(h, params["kernel"]).astype(jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return jax.nn.softmax(logits, axis=-1)


def lm_head_scores(
    params: Params, suffix_h: jax.Array, softcap: float | None = None
) -> jax.Array:
    """The reference's ``lm_head`` stage (``/root/reference/utils.py:287-290``):
    logits of the kept token, softmax -> next-token distribution.

    suffix_h: [S, 1, D] -> float32 scores [S, V]. ``softcap`` is Gemma2's
    final-logit softcapping, applied before the softmax. One-position slice
    of :func:`lm_head_scores_multi` (softmax is per-position, so slicing
    before or after is equivalent — one head implementation to maintain).
    """
    return lm_head_scores_multi(params, suffix_h, softcap)[:, 0]


# ---------------------------------------------------------------------------
# Whole-model forward (golden tests, training, monolithic path)
# ---------------------------------------------------------------------------

def head_params(params: Params) -> Params:
    """lm_head kernel, honouring tied embeddings (``/root/reference/utils.py:113``)."""
    if "lm_head" in params and params["lm_head"]:
        return params["lm_head"]
    return {"kernel": params["embed"]["embedding"].T}


def forward_full(
    params: Params,
    cfg: LlamaConfig,
    ids: jax.Array,
    dtype: jnp.dtype = jnp.float32,
    total_len=None,
) -> jax.Array:
    """Monolithic causal forward: ids [B, L] -> logits [B, L, V] (float32).

    Used by tests as the reference invariant (sharded layerwise forward must
    equal the monolithic forward) and by the training step. ``total_len``
    (longrope): defaults to L — HF's own batch forward selects the
    long/short table from the padded batch length (max position id + 1),
    so the default reproduces an HF forward on these exact ids.
    """
    cfg.require_single_visit("the monolithic forward (forward_full, training)")
    b, l = ids.shape
    if total_len is None and cfg.rope_scaling_kind == "longrope":
        total_len = jnp.int32(l)
    x = embed(params["embed"], ids, dtype, cfg)
    positions = jnp.arange(l)
    full = causal_mask(l, l)
    banded = causal_mask(
        l, l, window=cfg.sliding_window, chunk=cfg.attention_chunk_size
    )
    pattern = layer_sliding_pattern(cfg)
    rope_pat = layer_rope_pattern(cfg)
    decay = layer_log_decay(cfg)
    limits = layer_swiglu_limits(cfg)
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        for i, lp in enumerate(layers):
            x = decoder_layer(
                lp, cfg, x, positions,
                banded if pattern[i] else full,
                sliding=pattern[i], rope_on=rope_pat[i], total_len=total_len,
                log_decay=None if decay is None else jnp.asarray(decay[i]),
                limits=None if limits is None else jnp.asarray(limits[i]),
            )
    else:  # stacked pytree with leading layer axis -> scan (one compile)
        if decay is not None:
            raise NotImplementedError(
                "a model with linear-attention layers does not stack into one scan"
            )
        flags = jnp.asarray(pattern)
        rflags = jnp.asarray(rope_pat)

        def body(h, xs):
            layer_params, sl, ro, lim = xs
            mask = jnp.where(sl, banded, full)
            return (
                decoder_layer(
                    layer_params, cfg, h, positions, mask, sliding=sl,
                    rope_on=ro, total_len=total_len, limits=lim,
                ),
                None,
            )

        x, _ = jax.lax.scan(
            body, x, (layers, flags, rflags, None if limits is None else jnp.asarray(limits))
        )
    x = final_norm(params["norm"], cfg, x)
    logits = _mm(x, head_params(params)["kernel"]).astype(jnp.float32)
    if cfg.final_logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_logit_softcap) * cfg.final_logit_softcap
    return logits


# ---------------------------------------------------------------------------
# Initialisation (tests / training-from-scratch)
# ---------------------------------------------------------------------------

def init_layer_params(
    rng: jax.Array, cfg: LlamaConfig, dtype=jnp.float32, sliding: bool = False,
    linear: bool = False,
) -> Params:
    """``sliding`` / ``linear``: the layer's kind, for a model whose local
    or linear-attention layers have their own attention shape, sink, output
    norm or gate (``cfg.attn_shape``)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    nq, nkv, hd, vd = cfg.attn_shape(sliding, linear)
    ks = jax.random.split(rng, 14)

    def lin(key, fan_in, fan_out):
        scale = (2.0 / (fan_in + fan_out)) ** 0.5
        return (jax.random.normal(key, (fan_in, fan_out)) * scale).astype(dtype)

    def bias(key, n):
        return (jax.random.normal(key, (n,)) * 0.02).astype(dtype)

    if cfg.kv_lora_rank:
        # MLA (DeepSeek): LoRA'd q when q_lora_rank is set, compressed KV
        # always; wo reads the heads' v_head_dim-wide outputs.
        mks = jax.random.split(ks[0], 6)
        attn = {
            "kv_a": lin(mks[0], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
            "kv_b": lin(
                mks[1], cfg.kv_lora_rank, nq * (cfg.qk_nope_head_dim + cfg.v_dim)
            ),
            "wo": lin(ks[3], nq * cfg.v_dim, d),
        }
        if cfg.q_lora_rank:
            attn |= {
                "q_a": lin(mks[2], d, cfg.q_lora_rank),
                "q_a_norm": jnp.ones((cfg.q_lora_rank,), dtype),
                "q_b": lin(mks[3], cfg.q_lora_rank, nq * hd),
            }
        else:
            attn["wq"] = lin(mks[4], d, nq * hd)
        if cfg.attention_in_bias:
            # HF deepseek attention_bias: q_a_proj and kv_a_proj_with_mqa
            # only — the dense q_proj is bias=False unconditionally.
            attn["bkv_a"] = bias(ks[8], cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            if cfg.q_lora_rank:
                attn["bq_a"] = bias(ks[7], cfg.q_lora_rank)
    else:
        attn = {
            "wq": lin(ks[0], d, nq * hd),
            "wk": lin(ks[1], d, nkv * hd),
            "wv": lin(ks[2], d, nkv * vd),
            "wo": lin(ks[3], nq * vd, d),
        }
        if cfg.attn_sink_local if sliding else cfg.attn_sink_global:
            attn["sink"] = jax.random.normal(
                jax.random.fold_in(rng, 77), (nq,)
            ).astype(dtype)
    if cfg.attention_in_bias and not cfg.kv_lora_rank:
        attn |= {
            "bq": bias(ks[7], nq * hd),
            "bk": bias(ks[8], nkv * hd),
            "bv": bias(ks[9], nkv * vd),
        }
    if cfg.attention_out_bias:
        attn["bo"] = bias(ks[10], d)
    if cfg.qk_norm:
        attn |= {"q_norm": jnp.ones((hd,), dtype), "k_norm": jnp.ones((hd,), dtype)}
    if linear and cfg.linear_output_norm:
        attn["o_norm"] = jnp.ones((vd,), dtype)
    if cfg.linear_output_gate if linear else cfg.attn_output_gate:
        attn["wg"] = lin(jax.random.fold_in(rng, 78), d, nq * vd)
    if cfg.num_local_experts:
        e = cfg.num_local_experts

        def elin(key, fan_in, fan_out):
            # Every expert is drawn, the held ones kept: a share's weights
            # are the uncut layer's own (the held ids' slices).
            scale = (2.0 / (fan_in + fan_out)) ** 0.5
            held = cfg.held_experts
            w = jax.random.normal(key, (e, fan_in, fan_out)) * scale
            return w[held.start : held.stop].astype(dtype)

        mlp = {
            "router": lin(ks[4], d, e),
            "gate": elin(ks[5], d, f),
            "up": elin(ks[6], d, f),
            "down": elin(ks[11], f, d),
        }
    else:
        mlp = {
            "gate": lin(ks[4], d, f),
            "up": lin(ks[5], d, f),
            "down": lin(ks[6], f, d),
        }
        if cfg.mlp_bias:
            mlp |= {"bgate": bias(ks[11], f), "bup": bias(ks[12], f), "bdown": bias(ks[13], d)}
    out = {
        "input_layernorm": {"scale": jnp.ones((d,), dtype)},
        "post_attention_layernorm": {"scale": jnp.ones((d,), dtype)},
        "attn": attn,
        "mlp": mlp,
    }
    if cfg.ffw_sandwich_norms:
        out["pre_feedforward_layernorm"] = {"scale": jnp.ones((d,), dtype)}
        out["post_feedforward_layernorm"] = {"scale": jnp.ones((d,), dtype)}
    return out


def init_mixed_params(rng: jax.Array, cfg: LlamaConfig, dtype=jnp.float32) -> Params:
    """Random params for a MIXED dense/MoE stack (``cfg.moe_layer_pattern``):
    dense layers at the family's dense width (llama4 ``intermediate_size_mlp``),
    MoE layers with stacked experts — plus llama4's shared expert. Used by
    tests and the multichip dryrun to build the checkpoint structure the
    splitter produces from real llama4/qwen3_moe weights."""
    import dataclasses

    assert cfg.moe_layer_pattern is not None
    dense_cfg = dataclasses.replace(
        cfg,
        model_type="llama",
        num_local_experts=0,
        intermediate_size=cfg.intermediate_size_mlp or cfg.intermediate_size,
        moe_layer_pattern=None,
        intermediate_size_mlp=None,
    )
    moe_cfg = dataclasses.replace(cfg, moe_layer_pattern=None)
    keys = jax.random.split(rng, cfg.num_hidden_layers)
    layers = []
    sliding = layer_sliding_pattern(cfg)
    for i, is_moe in enumerate(cfg.moe_layer_pattern):
        lp = init_layer_params(
            keys[i], moe_cfg if is_moe else dense_cfg, dtype, sliding=sliding[i]
        )
        if is_moe and cfg.model_type == "mimo_v2_flash":
            # The DeepSeek router's correction bias, no shared expert.
            lp["mlp"]["correction_bias"] = (
                jax.random.normal(
                    jax.random.fold_in(keys[i], 99), (cfg.num_local_experts,)
                ) * 0.1
            ).astype(jnp.float32)
        if is_moe and cfg.model_type in ("llama4_text", "deepseek_v3"):
            d, f = cfg.hidden_size, cfg.intermediate_size
            if cfg.model_type == "deepseek_v3":
                # DeepSeek's shared expert is ONE MLP of n_shared_experts x
                # the routed width (V2 checkpoints: 2x; 0 builds zero-width
                # weights that contribute nothing).
                f *= cfg.n_shared_experts
            ks = jax.random.split(jax.random.fold_in(keys[i], 99), 4)

            def lin(key, fan_in, fan_out):
                scale = (2.0 / (fan_in + fan_out)) ** 0.5
                return (jax.random.normal(key, (fan_in, fan_out)) * scale).astype(dtype)

            lp["mlp"] |= {
                "shared_gate": lin(ks[0], d, f),
                "shared_up": lin(ks[1], d, f),
                "shared_down": lin(ks[2], f, d),
            }
            if cfg.model_type == "deepseek_v3":
                lp["mlp"]["correction_bias"] = (
                    jax.random.normal(ks[3], (cfg.num_local_experts,)) * 0.1
                ).astype(jnp.float32)
        layers.append(lp)
    # embed/norm/lm_head only — a 0-layer view skips building (and then
    # discarding) a full dense layer stack.
    params = init_params(
        jax.random.fold_in(rng, 1),
        dataclasses.replace(dense_cfg, num_hidden_layers=0),
        dtype,
    )
    params["layers"] = layers
    return params


def init_params(rng: jax.Array, cfg: LlamaConfig, dtype=jnp.float32) -> Params:
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params: Params = {
        "embed": {
            "embedding": (
                jax.random.normal(keys[0], (cfg.vocab_size, cfg.hidden_size)) * 0.02
            ).astype(dtype)
        },
        "layers": [
            init_layer_params(
                keys[i + 1], cfg, dtype,
                linear=cfg.layer_linear is not None and cfg.layer_linear[i],
            )
            for i in range(cfg.num_hidden_layers)
        ],
        "norm": {"scale": jnp.ones((cfg.hidden_size,), dtype)},
    }
    if cfg.total_ut_steps > 1:  # a looped stack's exit gate, beside the norm
        params["norm"]["gate"] = {
            "kernel": (
                jax.random.normal(
                    jax.random.fold_in(rng, 7), (cfg.hidden_size, 1)
                ) * 0.02
            ).astype(dtype),
            "bias": jnp.zeros((1,), dtype),
        }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {
            "kernel": (
                jax.random.normal(keys[-1], (cfg.hidden_size, cfg.vocab_size)) * 0.02
            ).astype(dtype)
        }
    return params
