"""Checkpoint preparation and per-layer loading.

The reference ships an offline splitter (``/root/reference/prepare_weights.py:12-49``)
that groups a HF ``pytorch_model.bin`` checkpoint's keys by their first three
dotted components and emits one ``{layer}.safetensors`` per top-level module
(``model.embed_tokens``, ``model.layers.{i}``, ``model.norm``, ``lm_head``),
copying tokenizer/config files alongside. The streaming executor then consumes
those per-layer files one at a time (``/root/reference/utils.py:126-127``).

This module keeps that exact file contract (same names, same grouping rule:
``'.'.join(key.split('.')[:3])``, same incremental shard loading so peak host
RAM stays at a couple of HF shards) and extends it TPU-first:

- Input can be ``.bin`` (torch) or ``.safetensors`` HF checkpoints, indexed or
  single-file.
- Output tensors are stored in the framework's *native layout* — linear
  kernels pre-transposed to [in, out] and renamed to the pytree layout of
  ``models/llama.py`` — so the hot load path is a zero-copy mmap + device_put
  with no host-side transposes. (Layer files produced by the *reference's*
  own ``prepare_weights.py`` — HF key names, [out, in] kernels — also load:
  :func:`load_layer` converts on the fly.)
"""

from __future__ import annotations

import gc
import json
import os
import re
import shutil
from glob import glob
from typing import Any, Callable, Iterator

import numpy as np

try:  # bf16 numpy arrays
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BFLOAT16 = None

from safetensors import safe_open
from safetensors.numpy import load_file as st_load_file
from safetensors.numpy import save_file as st_save_file

from flexible_llm_sharding_tpu.config import LlamaConfig
from flexible_llm_sharding_tpu.integrity import manifest as integrity_manifest

LAYER_FILE_SUFFIX = ".safetensors"
NATIVE_LAYOUT_MARKER = "fls_tpu_layout.json"

# int8 weight compression: a quantized tensor is stored as `{key}` (int8)
# plus `{key}::scale` (float32, one scale per output channel = the last axis
# of the native [in, out] layout); load_layer regroups the pair into a
# {"q8", "s"} leaf-group that the executor dequantizes ON DEVICE after the
# host->HBM transfer — the link carries half the bytes, which is the whole
# point in the transfer-bound streaming regime. Opt-in
# (``split_into_layers(dtype='int8')``), approximate (symmetric per-channel
# round-to-nearest), and self-describing via the layout marker.
QUANT_SCALE_SUFFIX = "::scale"

# int4: two values pack per byte along the IN axis, with GROUP-WISE scales
# along that axis (per-output-channel alone is too coarse at 4 bits; the
# group bounds each weight's error by its neighbours' amax, the standard
# int4 recipe). A quantized tensor stores `{key}` (packed uint8, in/2) +
# `{key}::scale4` (fp32 [.., in/group, out]) and reaches the device as a
# {"q4","s"} leaf-group — HALF of int8's bytes over the host->HBM link,
# the binding constraint of the streaming regime. Tensors whose in-dim
# doesn't divide the group fall back to per-output-channel int8 (the
# ordinary _quantize_int8 layout); the leaves self-describe either way.
QUANT4_SCALE_SUFFIX = "::scale4"
INT4_GROUP = 64


def is_float_like(a) -> bool:
    """True for real float dtypes AND the bfloat16 extension type — the
    ONE spelling of "does this tensor cast/quantize" shared by the
    quantizers, the dtype-kind derivation, and the planner (a second
    spelling drifting on a future fp8 addition is the failure mode)."""
    dt = np.asarray(a).dtype
    return np.issubdtype(dt, np.floating) or dt.name == "bfloat16"


def _quantize_int8(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8: returns (q [same shape], scale).

    2-D [in, out] kernels get one scale per output channel (the last axis).
    3-D [E, in, out] stacked MoE expert kernels get one scale per (expert,
    output channel) — amax over axis 1 only, scale [E, out] — so an expert
    with small weights does not inherit the largest expert's scale (which
    would inflate its quantization error well beyond the dense per-channel
    level)."""
    w32 = np.asarray(w, np.float32)
    reduce_axes = tuple(range(w32.ndim - 1)) if w32.ndim < 3 else (1,)
    amax = np.max(np.abs(w32), axis=reduce_axes)
    scale = np.maximum(amax, 1e-12).astype(np.float32) / 127.0
    q = np.clip(
        np.rint(w32 / scale.reshape(_scale_expand(scale, w32.ndim))), -127, 127
    ).astype(np.int8)
    return q, scale


def _scale_expand(scale: np.ndarray, q_ndim: int):
    """Broadcast shape for a quantization scale against its int8 payload:
    the scale keeps the payload's leading axes (stack/expert) and trailing
    channel axis; the reduced middle axes become size 1. Covers all four
    layouts — stored [out] / stacked [k, out] / per-expert [E, out] /
    stacked-per-expert [k, E, out]."""
    return scale.shape[:-1] + (1,) * (q_ndim - scale.ndim) + scale.shape[-1:]


def _quantize_int4(
    w: np.ndarray, group: int = INT4_GROUP
) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric group-wise int4 along the IN axis (axis -2): values in
    [-7, 7] stored offset-binary (nibble = q + 8), packed two per byte along
    the in axis (low nibble = even index). Returns (packed uint8
    [.., in/2, out], scale fp32 [.., in/group, out]). Callers guarantee
    in % group == 0 (``_quantize_flat`` falls back to int8 otherwise)."""
    w32 = np.asarray(w, np.float32)
    *lead, n_in, n_out = w32.shape
    wg = w32.reshape(*lead, n_in // group, group, n_out)
    amax = np.max(np.abs(wg), axis=-2)
    scale = (np.maximum(amax, 1e-12) / 7.0).astype(np.float32)
    q = np.clip(np.rint(wg / scale[..., None, :]), -7, 7).astype(np.int8)
    q = q.reshape(*lead, n_in, n_out)
    nib = (q + 8).astype(np.uint8)
    return nib[..., 0::2, :] | (nib[..., 1::2, :] << 4), scale


def _quantize_flat(
    sd: dict[str, np.ndarray], dtype: str = "int8"
) -> dict[str, np.ndarray]:
    """Quantize one flat native state dict: matmul kernels (>= 2-D floats)
    quantize and gain a scale twin; 1-D tensors (norm scales, biases) are
    tiny and stay exact in float32. ``dtype`` 'int8' (per-output-channel)
    or 'int4' (group-wise + packed; kernels whose in-dim doesn't fit the
    group fall back to per-output-channel int8 for that tensor — leaves
    self-describe). The single rule shared by split_into_layers and
    requantize_native."""
    qd: dict[str, np.ndarray] = {}
    for k, v in sd.items():
        v = np.asarray(v)
        if v.ndim >= 2 and is_float_like(v):
            if dtype == "int4" and v.shape[-2] % INT4_GROUP == 0:
                q, sc = _quantize_int4(v)
                qd[k] = q
                qd[k + QUANT4_SCALE_SUFFIX] = sc
            else:
                q, sc = _quantize_int8(v)
                qd[k] = q
                qd[k + QUANT_SCALE_SUFFIX] = sc
        elif is_float_like(v) and v.dtype.itemsize < 4:
            # Sub-fp32 floats (bf16, fp16) up-cast EXACTLY to the
            # documented "1-D tensors stay exact in float32" contract —
            # fp16 passing through unchanged silently broke the
            # planner's byte estimates for fp16-source checkpoints.
            qd[k] = np.asarray(v, np.float32)
        else:
            qd[k] = v
    return qd


def is_quantized_leaf(node) -> bool:
    """True for BOTH quantized leaf-groups: int8 {"q8","s"} and int4
    {"q4","s"} — detection sites (loader cast, placement probe) treat them
    alike; kind-specific handling branches on :func:`quant_kind`."""
    return isinstance(node, dict) and set(node) in ({"q8", "s"}, {"q4", "s"})


def quant_kind(node) -> str:
    """'q8' or 'q4' for a quantized leaf-group."""
    return "q8" if "q8" in node else "q4"


def flat_dtype_kind(flat: dict[str, Any]) -> str:
    """Storage-dtype kind of one layer file's flat tensor dict — the ONE
    derivation shared by the manifest writer (``layer_entry`` records it
    per layer) and the load-path check (``load_layer`` compares it), so
    the two can never desync. 'int4' when any group-scale twin is
    present (int8 per-tensor fallbacks inside an int4 file keep the int4
    kind — leaves self-describe), 'int8' for per-channel scales, else
    the dtype name of the layer's largest float tensor ('bfloat16',
    'float32', ...) or 'none' for a float-free file."""
    keys = flat.keys()
    if any(k.endswith(QUANT4_SCALE_SUFFIX) for k in keys):
        return "int4"
    if any(k.endswith(QUANT_SCALE_SUFFIX) for k in keys):
        return "int8"
    best = None
    for k in sorted(keys):
        a = np.asarray(flat[k])
        if is_float_like(a):
            if best is None or a.nbytes > best.nbytes:
                best = a
    return best.dtype.name if best is not None else "none"


def simulate_quantized(a: np.ndarray, dtype: str) -> np.ndarray:
    """Quantize->dequantize round trip of ONE kernel under exactly the
    branch rule ``_quantize_flat`` materializes (int4 falls back to
    per-output-channel int8 when the in-dim is off the group) — float32
    out. The sensitivity probe (runtime/precisionplan.py) scores layers
    through this, so what it measures is what ``requantize_native``
    later writes and ``executor._dequant_tree`` later computes."""
    if dtype not in ("int8", "int4"):
        raise ValueError(f"simulate_quantized: unsupported dtype {dtype!r}")
    a32 = np.asarray(a, np.float32)
    if dtype == "int4" and a32.ndim >= 2 and a32.shape[-2] % INT4_GROUP == 0:
        q, s = _quantize_int4(a32)
        return dequantize_np({"q4": q, "s": s}).astype(np.float32)
    q, s = _quantize_int8(a32)
    return dequantize_np({"q8": q, "s": s}).astype(np.float32)


def dequant4_math(b, s, xp):
    """int4 unpack + group dequant, parameterized on the array module
    (numpy for host oracles, jax.numpy for the on-device kernel) — the
    SINGLE source of truth for the packing convention: low nibble = even
    in-index, offset-binary (nibble = q + 8), scales [.., in/g, out]."""
    lo = (b & 0xF).astype(xp.float32) - 8.0
    hi = (b >> 4).astype(xp.float32) - 8.0
    q = xp.stack([lo, hi], axis=-2)  # [.., in/2, 2, out]
    *lead, half, _, out = q.shape
    q = q.reshape(*lead, half * 2, out)
    n_groups = s.shape[-2]
    qg = q.reshape(*lead, n_groups, (half * 2) // n_groups, out)
    return (qg * s[..., None, :]).reshape(*lead, half * 2, out)


def dequantize_np(node: dict[str, np.ndarray]) -> np.ndarray:
    """Host-side dequantize of one quantized leaf-group (float32)."""
    if quant_kind(node) == "q4":
        return dequant4_math(
            np.asarray(node["q4"], np.uint8),
            np.asarray(node["s"], np.float32),
            np,
        )
    q = np.asarray(node["q8"], np.float32)
    s = np.asarray(node["s"])
    return q * s.reshape(_scale_expand(s, q.ndim))

# ---------------------------------------------------------------------------
# Key grouping — the reference's rule (/root/reference/prepare_weights.py:21)
# ---------------------------------------------------------------------------

def key_to_layer(key: str) -> str:
    """Group a flat HF param key into its top-level layer name.

    Same rule as the reference: strip ``.weight``/``.bias``, keep the first
    three dotted components (``model.layers.17.self_attn.q_proj.weight`` ->
    ``model.layers.17``; ``lm_head.weight`` -> ``lm_head``).
    """
    layer = ".".join(re.sub(r"\.(weight|bias)$", "", key).split(".")[:3])
    # A looped model's exit gate (Ouro's ``model.early_exit_gate``) is read
    # where the final norm is, at every step's end: it lives in the norm's file.
    return "model.norm" if layer == "model.early_exit_gate" else layer


def layer_names_for(num_hidden_layers: int, tie_word_embeddings: bool = False) -> list[str]:
    """Execution-ordered layer names (``/root/reference/utils.py:106-107``)."""
    names = (
        ["model.embed_tokens"]
        + [f"model.layers.{i}" for i in range(num_hidden_layers)]
        + ["model.norm"]
    )
    if not tie_word_embeddings:
        names.append("lm_head")
    return names


def layer_file_for(model_path: str, name: str, tied: bool = False) -> str:
    """The file a layer name actually reads: with tied embeddings,
    ``lm_head`` re-materialises from the embedding file. The ONE mapping
    shared by the streaming loader (quarantine keys, stat guards) and the
    residency planner's byte estimates — any change to the on-disk layout
    must keep both views identical or the planner silently desyncs from
    what the loader streams."""
    fname = "model.embed_tokens" if (name == "lm_head" and tied) else name
    return os.path.join(model_path, f"{fname}{LAYER_FILE_SUFFIX}")


# ---------------------------------------------------------------------------
# HF checkpoint enumeration (host side, offline)
# ---------------------------------------------------------------------------

def _hf_weight_map(src_dir: str) -> tuple[dict[str, str], str]:
    """Return ({param_key: shard_filename}, kind) for any HF checkpoint shape."""
    for index_name, kind in (
        ("model.safetensors.index.json", "safetensors"),
        ("pytorch_model.bin.index.json", "bin"),
    ):
        p = os.path.join(src_dir, index_name)
        if os.path.exists(p):
            with open(p) as f:
                return json.load(f)["weight_map"], kind
    for single, kind in (("model.safetensors", "safetensors"), ("pytorch_model.bin", "bin")):
        p = os.path.join(src_dir, single)
        if os.path.exists(p):
            if kind == "safetensors":
                with safe_open(p, framework="numpy") as f:
                    keys = list(f.keys())
            else:
                import torch

                keys = list(torch.load(p, map_location="meta", weights_only=True).keys())
            return {k: single for k in keys}, kind
    raise FileNotFoundError(f"No HF checkpoint found under {src_dir}")


def _load_shard(path: str, kind: str, want=None) -> dict[str, np.ndarray]:
    """Load one HF shard; ``want`` (key -> bool) selects keys. With
    safetensors unwanted tensors are never READ (multi-GB vision towers of
    a multimodal bundle never touch RAM); the torch format can only filter
    after a full load."""
    if kind == "safetensors":
        if want is None:
            return st_load_file(path)
        out = {}
        with safe_open(path, framework="numpy") as f:
            for k in f.keys():
                if want(k):
                    out[k] = f.get_tensor(k)
        return out
    import torch

    out = {}
    for k, t in torch.load(path, map_location="cpu", weights_only=True).items():
        if want is not None and not want(k):
            continue
        if t.dtype == torch.bfloat16:
            out[k] = t.view(torch.uint16).numpy().view(_BFLOAT16)
        else:
            out[k] = t.numpy()
    return out


# ---------------------------------------------------------------------------
# HF <-> native layout conversion
# ---------------------------------------------------------------------------

# (native flat key, HF sub-key, transpose?) for a decoder layer.
_LAYER_MAP = [
    ("input_layernorm.scale", "input_layernorm.weight", False),
    ("post_attention_layernorm.scale", "post_attention_layernorm.weight", False),
    ("attn.wq", "self_attn.q_proj.weight", True),
    ("attn.wk", "self_attn.k_proj.weight", True),
    ("attn.wv", "self_attn.v_proj.weight", True),
    ("attn.wo", "self_attn.o_proj.weight", True),
    ("mlp.gate", "mlp.gate_proj.weight", True),
    ("mlp.up", "mlp.up_proj.weight", True),
    ("mlp.down", "mlp.down_proj.weight", True),
]

# Bias vectors (1-D, no transpose), present only in some families (Qwen2
# q/k/v; Llama with attention_bias/mlp_bias). Consumed when the checkpoint
# has them, absent from the native file otherwise — models/llama.py treats
# bias presence as a trace-time structural fact.
_LAYER_MAP_OPTIONAL = [
    ("attn.bq", "self_attn.q_proj.bias"),
    ("attn.bk", "self_attn.k_proj.bias"),
    ("attn.bv", "self_attn.v_proj.bias"),
    ("attn.bo", "self_attn.o_proj.bias"),
    ("attn.q_norm", "self_attn.q_norm.weight"),  # qwen3 per-head-dim RMSNorm
    ("attn.k_norm", "self_attn.k_norm.weight"),
    # MiMo-V2: the learned per-head sink logit of the layers that have one
    ("attn.sink", "self_attn.attention_sink_bias"),
    # MiniCPM-SALA: the per-head RMSNorm on a linear-attention layer's output
    ("attn.o_norm", "self_attn.o_norm.weight"),
    # gemma2 sandwich norms around the MLP
    ("pre_feedforward_layernorm.scale", "pre_feedforward_layernorm.weight"),
    ("post_feedforward_layernorm.scale", "post_feedforward_layernorm.weight"),
    ("mlp.bgate", "mlp.gate_proj.bias"),
    ("mlp.bup", "mlp.up_proj.bias"),
    ("mlp.bdown", "mlp.down_proj.bias"),
]


# Non-parameter buffers that may appear in HF checkpoints and carry no weights.
_IGNORABLE_HF_SUFFIXES = ("rotary_emb.inv_freq",)


def _stack_experts(
    layer_name, prefix, name_map, sd, out, consumed, held=None
) -> None:
    """Stack per-expert Linear weights ``{prefix}.{e}.{hf_name}.weight`` into
    one transposed [E, in, out] native array per projection (the _moe_mlp
    einsum layout — one tensor per projection keeps a shard upload a single
    device_put). ``held``: the expert ids this process holds (expert
    parallelism's share, ``LlamaConfig.held_experts``); the others' tensors
    are consumed and left out. None = all."""
    probe = name_map[0][1]
    n_exp = 0
    while f"{prefix}.{n_exp}.{probe}.weight" in sd:
        n_exp += 1
    if not n_exp:
        raise ValueError(f"{layer_name}: MoE layer with no expert weights")
    keep = range(n_exp) if held is None else held
    if not set(keep) <= set(range(n_exp)):
        raise ValueError(
            f"{layer_name}: held experts {keep} of a layer with {n_exp}"
        )
    for native_key, hf_w in name_map:
        consumed.update(f"{prefix}.{ei}.{hf_w}.weight" for ei in range(n_exp))
        out[native_key] = np.ascontiguousarray(
            np.stack([sd[f"{prefix}.{ei}.{hf_w}.weight"].T for ei in keep])
        )


def held_experts_of(src_dir: str):
    """The expert ids the checkpoint's own config.json gives this process
    (``ep_size``/``ep_rank``: ``LlamaConfig.held_experts``), or None where
    it holds every expert or the directory has no parseable config."""
    from flexible_llm_sharding_tpu.config import LlamaConfig

    try:
        cfg = LlamaConfig.from_pretrained(src_dir)
    except (OSError, ValueError, NotImplementedError):
        return None
    return cfg.held_experts if cfg.moe_ep_size > 1 else None


def hf_layer_to_native(
    layer_name: str, sd: dict[str, np.ndarray], held_experts=None
) -> dict[str, np.ndarray]:
    """Convert one layer's HF-keyed state dict to native flat keys/layout.

    Projection biases (Qwen2 q/k/v; Llama attention_bias/mlp_bias) map to
    their native slots when present. Tensors with no slot at all (an unknown
    architecture's extras) raise instead of silently dropping.
    ``held_experts``: the routed experts to keep of an expert layer
    (``_stack_experts``); the router and its bias keep their full width.
    """
    if layer_name == "model.embed_tokens":
        return {"embedding": sd["model.embed_tokens.weight"]}
    if layer_name == "model.norm":
        out = {"scale": sd["model.norm.weight"]}
        if "model.early_exit_gate.weight" in sd:  # Ouro: Linear(hidden, 1)
            out["gate.kernel"] = np.ascontiguousarray(
                sd["model.early_exit_gate.weight"].T
            )
            out["gate.bias"] = sd["model.early_exit_gate.bias"]
        return out
    if layer_name == "lm_head":
        return {"kernel": np.ascontiguousarray(sd["lm_head.weight"].T)}
    moe = any(".block_sparse_moe." in k for k in sd)
    qmoe = f"{layer_name}.mlp.experts.0.gate_proj.weight" in sd  # qwen3_moe / deepseek
    fused = f"{layer_name}.self_attn.qkv_proj.weight" in sd  # phi3 layout
    ff = any(".feed_forward." in k for k in sd)  # llama4 naming
    ff_moe = f"{layer_name}.feed_forward.router.weight" in sd
    mla = f"{layer_name}.self_attn.kv_a_proj_with_mqa.weight" in sd  # deepseek
    out = {}
    consumed = set()
    if f"{layer_name}.input_layernorm_2.weight" in sd:
        # Ouro's four norms a layer, in the native sandwich slots (gemma2's):
        # x + input_layernorm_2(Attn(input_layernorm(x))), then
        # a + post_attention_layernorm_2(MLP(post_attention_layernorm(a))).
        # The family's ``post_attention_layernorm`` is the MLP's INPUT norm,
        # where the native slot of that name norms the attention's output.
        sd = dict(sd)
        for native_key, hf_sub in (
            ("post_feedforward_layernorm.scale", "post_attention_layernorm_2"),
            ("pre_feedforward_layernorm.scale", "post_attention_layernorm"),
            ("post_attention_layernorm.scale", "input_layernorm_2"),
        ):
            out[native_key] = sd.pop(f"{layer_name}.{hf_sub}.weight")
        # so that the generic map below finds its slot already filled
        sd[f"{layer_name}.post_attention_layernorm.weight"] = out[
            "post_attention_layernorm.scale"
        ]
    for native_key, hf_sub, transpose in _LAYER_MAP:
        if (moe or ff or qmoe) and native_key.startswith("mlp."):
            continue  # Mixtral / llama4 / qwen3_moe expert layouts below
        if fused and native_key in (
            "attn.wq", "attn.wk", "attn.wv", "mlp.gate", "mlp.up"
        ):
            continue  # carried fused; split below
        if mla and native_key in ("attn.wq", "attn.wk", "attn.wv"):
            continue  # MLA projections mapped below (wq only when dense q)
        key = f"{layer_name}.{hf_sub}"
        w = sd[key]
        consumed.add(key)
        out[native_key] = np.ascontiguousarray(w.T) if transpose else w
    if mla:
        # DeepSeek multi-head latent attention (DeepseekV3Attention):
        # q either dense (q_proj) or LoRA (q_a -> norm -> q_b); KV always
        # compressed (kv_a_proj_with_mqa -> norm -> kv_b). Kernels store
        # [in, out] like every other native projection.
        def take(native_key, hf_sub, transpose=True, optional=False):
            key = f"{layer_name}.self_attn.{hf_sub}"
            if key not in sd:
                if optional:
                    return
                raise KeyError(f"{layer_name}: missing MLA tensor {key}")
            w = sd[key]
            consumed.add(key)
            out[native_key] = np.ascontiguousarray(w.T) if transpose else w

        if f"{layer_name}.self_attn.q_proj.weight" in sd:
            take("attn.wq", "q_proj.weight")
        else:
            take("attn.q_a", "q_a_proj.weight")
            take("attn.q_a_norm", "q_a_layernorm.weight", transpose=False)
            take("attn.q_b", "q_b_proj.weight")
            take("attn.bq_a", "q_a_proj.bias", transpose=False, optional=True)
        take("attn.kv_a", "kv_a_proj_with_mqa.weight")
        take("attn.kv_a_norm", "kv_a_layernorm.weight", transpose=False)
        take("attn.kv_b", "kv_b_proj.weight")
        take("attn.bkv_a", "kv_a_proj_with_mqa.bias", transpose=False, optional=True)
    if fused:
        # Phi3 fuses q/k/v into qkv_proj [(nq+2*nkv)*hd, D] and gate/up into
        # gate_up_proj [2F, D]. The split needs no config: o_proj's input
        # width IS nq*hd, and the two kv blocks share the remainder equally.
        qkv = sd[f"{layer_name}.self_attn.qkv_proj.weight"]
        consumed.add(f"{layer_name}.self_attn.qkv_proj.weight")
        nq_hd = out["attn.wo"].shape[0]  # [nq*hd, D] after transpose
        nkv_hd = (qkv.shape[0] - nq_hd) // 2
        if qkv.shape[0] != nq_hd + 2 * nkv_hd:
            raise ValueError(
                f"{layer_name}: qkv_proj rows {qkv.shape[0]} do not split "
                f"into q={nq_hd} + 2*kv (o_proj implies nq*hd={nq_hd})"
            )
        out["attn.wq"] = np.ascontiguousarray(qkv[:nq_hd].T)
        out["attn.wk"] = np.ascontiguousarray(qkv[nq_hd : nq_hd + nkv_hd].T)
        out["attn.wv"] = np.ascontiguousarray(qkv[nq_hd + nkv_hd :].T)
        gu = sd[f"{layer_name}.mlp.gate_up_proj.weight"]
        consumed.add(f"{layer_name}.mlp.gate_up_proj.weight")
        f_dim = gu.shape[0] // 2
        out["mlp.gate"] = np.ascontiguousarray(gu[:f_dim].T)
        out["mlp.up"] = np.ascontiguousarray(gu[f_dim:].T)
    gate_key = f"{layer_name}.self_attn.o_gate.weight"
    if gate_key in sd:  # MiniCPM-SALA: the output gate's kernel, both kinds
        consumed.add(gate_key)
        out["attn.wg"] = np.ascontiguousarray(sd[gate_key].T)
    if f"{layer_name}.self_attn.A_log" in sd:
        # GLM-5.3's KDA layers (names assumed, as the benchmark's
        # configuration file says): the short convolutions' taps [C, 1, K] ->
        # [K, C], the decay's and the output gate's two-matrix waists, the
        # per-head decay rate and its bias, beta's projection.
        for native_key, hf_sub, how in (
            ("attn.conv_q", "q_conv1d.weight", "taps"),
            ("attn.conv_k", "k_conv1d.weight", "taps"),
            ("attn.conv_v", "v_conv1d.weight", "taps"),
            ("attn.f_a", "f_a_proj.weight", "t"),
            ("attn.f_b", "f_b_proj.weight", "t"),
            ("attn.wg_a", "g_a_proj.weight", "t"),
            ("attn.wg_b", "g_b_proj.weight", "t"),
            ("attn.wb", "b_proj.weight", "t"),
            ("attn.A_log", "A_log", ""),
            ("attn.dt_bias", "dt_bias", ""),
        ):
            key = f"{layer_name}.self_attn.{hf_sub}"
            consumed.add(key)
            w = sd[key]
            if how == "taps":
                w = w.reshape(w.shape[0], -1)
            out[native_key] = np.ascontiguousarray(w.T) if how else w
    for sub in ("attn", "mlp"):
        # mHC, one set a sublayer: the mixes' projection, bias and scalars.
        key = f"{layer_name}.{sub}_hc.fn.weight"
        if key in sd:
            out[f"hc_{sub}.phi"] = np.ascontiguousarray(sd[key].T)
            out[f"hc_{sub}.b"] = sd[f"{layer_name}.{sub}_hc.base"]
            out[f"hc_{sub}.a"] = sd[f"{layer_name}.{sub}_hc.scale"]
            consumed.update(
                (key, f"{layer_name}.{sub}_hc.base", f"{layer_name}.{sub}_hc.scale")
            )
    # The sparse layers' indexer ranks keys only past index_topk tokens,
    # where the model is refused (runtime/tokenization.check_dense_len): its
    # tensors are left out of the layer files.
    consumed.update(k for k in sd if k.startswith(f"{layer_name}.self_attn.indexer."))
    for native_key, hf_sub in _LAYER_MAP_OPTIONAL:
        if mla and native_key in ("attn.bq", "attn.bk", "attn.bv"):
            continue  # HF MLA projections are bias-free (q_a/kv_a aside)
        key = f"{layer_name}.{hf_sub}"
        if key in sd:
            consumed.add(key)
            out[native_key] = sd[key]
    if ff and not ff_moe:
        # Llama4 dense layer: feed_forward.{gate,up,down}_proj (its dense
        # layers use intermediate_size_mlp, distinct from the experts').
        for native_key, sub in (
            ("mlp.gate", "gate_proj"), ("mlp.up", "up_proj"), ("mlp.down", "down_proj")
        ):
            key = f"{layer_name}.feed_forward.{sub}.weight"
            out[native_key] = np.ascontiguousarray(sd[key].T)
            consumed.add(key)
    if ff_moe:
        # Llama4 MoE layer: experts.gate_up_proj [E, D, 2F] (ALREADY
        # [in, out] per expert — a Parameter, not a Linear) splits into
        # gate/up; experts.down_proj [E, F, D] passes through; router
        # [E, D] and the shared expert's Linears transpose as usual.
        gu = sd[f"{layer_name}.feed_forward.experts.gate_up_proj"]
        consumed.add(f"{layer_name}.feed_forward.experts.gate_up_proj")
        f_dim = gu.shape[-1] // 2
        out["mlp.gate"] = np.ascontiguousarray(gu[..., :f_dim])
        out["mlp.up"] = np.ascontiguousarray(gu[..., f_dim:])
        dk = f"{layer_name}.feed_forward.experts.down_proj"
        out["mlp.down"] = sd[dk]
        consumed.add(dk)
        rk = f"{layer_name}.feed_forward.router.weight"
        out["mlp.router"] = np.ascontiguousarray(sd[rk].T)
        consumed.add(rk)
        for native_key, sub in (
            ("mlp.shared_gate", "gate_proj"),
            ("mlp.shared_up", "up_proj"),
            ("mlp.shared_down", "down_proj"),
        ):
            key = f"{layer_name}.feed_forward.shared_expert.{sub}.weight"
            out[native_key] = np.ascontiguousarray(sd[key].T)
            consumed.add(key)
    if qmoe:
        # Qwen3-MoE / DeepSeek / MiMo-V2: router at mlp.gate [E, D] -> [D, E];
        # per-expert gate/up/down Linears stack into the same
        # [E, D, F] / [E, F, D] native arrays as Mixtral. DeepSeek adds a
        # routing correction-bias buffer and a shared expert.
        rk = f"{layer_name}.mlp.gate.weight"
        out["mlp.router"] = np.ascontiguousarray(sd[rk].T)
        consumed.add(rk)
        _stack_experts(
            layer_name, f"{layer_name}.mlp.experts",
            (("mlp.gate", "gate_proj"), ("mlp.up", "up_proj"), ("mlp.down", "down_proj")),
            sd, out, consumed, held_experts,
        )
        bk = f"{layer_name}.mlp.gate.e_score_correction_bias"
        if bk in sd:
            out["mlp.correction_bias"] = sd[bk]
            consumed.add(bk)
        for native_key, sub in (
            ("mlp.shared_gate", "gate_proj"),
            ("mlp.shared_up", "up_proj"),
            ("mlp.shared_down", "down_proj"),
        ):
            key = f"{layer_name}.mlp.shared_experts.{sub}.weight"
            if key in sd:
                out[native_key] = np.ascontiguousarray(sd[key].T)
                consumed.add(key)
    if moe:
        # Mixtral MoE: router [E, D] -> [D, E]; per-expert w1 (gate) / w3
        # (up) [F, D] and w2 (down) [D, F] stack into [E, D, F] / [E, F, D]
        # native arrays (models/llama.py _moe_mlp layout) — one tensor per
        # projection so a shard upload stays a single device_put.
        rk = f"{layer_name}.block_sparse_moe.gate.weight"
        out["mlp.router"] = np.ascontiguousarray(sd[rk].T)
        consumed.add(rk)
        _stack_experts(
            layer_name, f"{layer_name}.block_sparse_moe.experts",
            (("mlp.gate", "w1"), ("mlp.up", "w3"), ("mlp.down", "w2")),
            sd, out, consumed,
        )
    leftover = {
        k for k in sd.keys() - consumed if not k.endswith(_IGNORABLE_HF_SUFFIXES)
    }
    if leftover:
        raise ValueError(
            f"{layer_name}: tensors {sorted(leftover)} have no native-layout slot"
        )
    return out


def native_to_pytree(layer_name: str, flat: dict[str, np.ndarray]) -> dict[str, Any]:
    """Unflatten dotted native keys into the nested pytree of models/llama.py."""
    tree: dict[str, Any] = {}
    for k, v in flat.items():
        node = tree
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def _is_native(sd_keys) -> bool:
    return not any(k.startswith(("model.", "lm_head")) for k in sd_keys)


# ---------------------------------------------------------------------------
# The offline splitter (prepare_weights equivalent)
# ---------------------------------------------------------------------------

# Multimodal wrapper checkpoints (Gemma-3, Llama-4): the published weights
# are usually the vision+text bundle; scoring wants the text tower. The
# splitter extracts it: language-model keys remap to the plain text layout,
# vision/projector keys drop, and the emitted config.json is the nested
# text_config (so the split dir IS a text checkpoint). The wrapper->text
# config rule itself lives in config.extract_text_config, shared with
# LlamaConfig.from_hf_config.
_MM_DROP_PREFIXES = (
    "model.vision_tower.",
    "model.multi_modal_projector.",
    "model.vision_model.",
    "vision_tower.",
    "vision_model.",
    "multi_modal_projector.",
)


def _multimodal_remap(src_dir: str):
    """(remap_fn, text_config dict) for a multimodal wrapper checkpoint, or
    (None, None) for plain text checkpoints. remap_fn: original HF key ->
    text-model key, or None for dropped (vision/projector) keys."""
    from flexible_llm_sharding_tpu.config import extract_text_config

    cfg_path = os.path.join(src_dir, "config.json")
    if not os.path.exists(cfg_path):
        return None, None
    with open(cfg_path) as f:
        d = json.load(f)
    tc = extract_text_config(d)
    if tc is None:
        return None, None

    def remap(k: str):
        for p in _MM_DROP_PREFIXES:
            if k.startswith(p):
                return None
        # transformers >= 4.52 nests the tower as model.language_model.*;
        # older exports used language_model.model.* (+ language_model.lm_head).
        if k.startswith("model.language_model."):
            return "model." + k[len("model.language_model."):]
        if k.startswith("language_model.model."):
            return "model." + k[len("language_model.model."):]
        if k.startswith("language_model.lm_head"):
            return k[len("language_model."):]
        return k  # lm_head.* and any already-plain keys

    return remap, tc


def split_into_layers(
    src_dir: str,
    out_dir: str,
    dtype: str | None = None,
    layout: str = "native",
    progress: Callable[[str], None] | None = None,
) -> list[str]:
    """HF checkpoint dir -> per-layer safetensors files + copied aux files.

    Mirrors ``/root/reference/prepare_weights.py:12-49``: aux (non-weight)
    files copied first; layers emitted in ascending (min shard id, #shards)
    order; HF shards loaded incrementally and freed as their keys are
    consumed, keeping peak RAM to ~a couple of shards.

    dtype: optionally cast (e.g. 'bfloat16' — the TPU-preferred storage type).
    layout: 'native' (pre-transposed, renamed) or 'hf' (reference-identical).
    Returns the ordered list of emitted layer names.
    """
    if layout not in ("native", "hf"):
        raise ValueError(f"layout must be 'native' or 'hf', got {layout!r}")
    os.makedirs(out_dir, exist_ok=True)
    for fn in glob(f"{src_dir}/*"):
        base = os.path.basename(fn)
        if (
            os.path.isfile(fn)
            and ".bin" not in base
            and not base.endswith(".safetensors")
            and not base.endswith(".index.json")
        ):
            shutil.copy(fn, os.path.join(out_dir, base))

    weight_map, kind = _hf_weight_map(src_dir)

    remap, text_cfg = _multimodal_remap(src_dir)
    if remap is not None:
        # Extracting the text tower from a vision+text bundle: drop the
        # vision/projector keys, rename language-model keys to the plain
        # text layout, and emit the nested text_config as the config.
        renamed: dict[str, str] = {}
        for k in list(weight_map):
            nk = remap(k)
            if nk is None:
                del weight_map[k]
            elif nk != k:
                renamed[k] = nk
        weight_map = {renamed.get(k, k): v for k, v in weight_map.items()}
        with open(os.path.join(out_dir, "config.json"), "w") as f:
            json.dump(text_cfg, f, indent=1)
    layer2keys: dict[str, set[str]] = {}
    for k in weight_map:
        layer2keys.setdefault(key_to_layer(k), set()).add(k)
    layer2shards = {
        layer: {weight_map[k] for k in keys} for layer, keys in layer2keys.items()
    }
    # Reference ordering rule (/root/reference/prepare_weights.py:28).
    shard_ids = {s: i for i, s in enumerate(sorted({s for ss in layer2shards.values() for s in ss}))}
    layer_list = sorted(
        layer2shards,
        key=lambda l: (min(shard_ids[s] for s in layer2shards[l]), len(layer2shards[l])),
    )

    held = held_experts_of(src_dir) if layout == "native" else None
    quantize = dtype in ("int8", "int4")
    if quantize and layout != "native":
        raise ValueError(f"dtype='{dtype}' requires layout='native'")
    if dtype == "bfloat16":
        if _BFLOAT16 is None:
            raise ImportError("dtype='bfloat16' requires ml_dtypes")
        cast = _BFLOAT16
    elif quantize:
        cast = None  # quantized below, after the native-layout conversion
    else:
        cast = np.dtype(dtype) if dtype is not None else None

    state: dict[str, np.ndarray] = {}
    loaded: set[str] = set()
    manifest_layers: dict[str, dict] = {}
    for layer in layer_list:
        for shard in layer2shards[layer] - loaded:
            loaded.add(shard)
            # Selective read: dropped (vision/projector) keys are skipped at
            # the safetensors layer, so a bundle's vision tower never
            # materialises in RAM.
            want = (
                (lambda k: remap(k) is not None) if remap is not None else None
            )
            for k, v in _load_shard(
                os.path.join(src_dir, shard), kind, want=want
            ).items():
                nk = remap(k) if remap is not None else k
                state[nk] = v
        missing = layer2keys[layer] - state.keys()
        if missing:
            raise KeyError(
                f"{layer}: keys {sorted(missing)} listed in the index but absent "
                f"from shards {sorted(layer2shards[layer])}"
            )
        sd = {k: state[k] for k in layer2keys[layer]}
        if cast is not None:
            sd = {
                k: np.asarray(v, dtype=cast) if is_float_like(v) else v
                for k, v in sd.items()
            }
        if layout == "native":
            sd = hf_layer_to_native(layer, sd, held)
        if quantize:
            sd = _quantize_flat(sd, dtype)
        stored = {k: np.ascontiguousarray(v) for k, v in sd.items()}
        st_save_file(stored, os.path.join(out_dir, f"{layer}{LAYER_FILE_SUFFIX}"))
        # Per-layer content checksums over the EXACT stored bytes — the
        # loader verifies every subsequent read against this manifest
        # (integrity/manifest.py; written atomically after the last layer).
        manifest_layers[layer] = integrity_manifest.layer_entry(
            stored, f"{layer}{LAYER_FILE_SUFFIX}"
        )
        del stored
        for k in layer2keys[layer]:
            del state[k]
        del sd
        gc.collect()
        if progress:
            progress(layer)

    with open(os.path.join(out_dir, NATIVE_LAYOUT_MARKER), "w") as f:
        json.dump({"layout": layout, "dtype": dtype, "layers": layer_list}, f)
    integrity_manifest.write_manifest(out_dir, manifest_layers)
    return layer_list


# ---------------------------------------------------------------------------
# Per-layer loading (the streaming hot path, host side)
# ---------------------------------------------------------------------------

# safetensors dtype tag -> numpy dtype (BF16 via ml_dtypes).
_ST_DTYPES: dict[str, Any] = {
    "F64": np.float64,
    "F32": np.float32,
    "F16": np.float16,
    "I64": np.int64,
    "I32": np.int32,
    "I16": np.int16,
    "I8": np.int8,
    "U8": np.uint8,
    "BOOL": np.bool_,
}
if _BFLOAT16 is not None:
    _ST_DTYPES["BF16"] = _BFLOAT16


def safetensors_header(path: str) -> tuple[dict[str, dict], int]:
    """Parse a safetensors file's header WITHOUT touching the payload:
    ``({key: {"dtype": tag, "shape": [...], "data_offsets": [b, e]}},
    payload_base_offset)``. One small read — byte-accounting estimators
    (``residency.layer_stream_bytes``) use it to see a layer's stored
    shapes/dtypes without faulting a multi-GB payload into RAM."""
    with open(path, "rb") as f:
        n = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(n))
    header.pop("__metadata__", None)
    return header, 8 + n


def _mmap_safetensors(path: str) -> dict[str, np.ndarray]:
    """True zero-copy safetensors read: parse the header, then return
    read-only ``np.memmap`` views into the payload.

    ``safetensors.numpy.load_file`` copies every tensor into a fresh buffer;
    on the streaming hot path that is a full extra pass over the model per
    stream (13.5 GB of memcpy for a 7B). A view costs nothing up front — the
    pages fault in from the page cache (kept warm by the native readahead
    pool) *during* the host->HBM ``device_put``, overlapping disk I/O with
    the transfer itself. Falls back to the library loader for any dtype tag
    this table doesn't know.
    """
    header, base = safetensors_header(path)
    if any(m["dtype"] not in _ST_DTYPES for m in header.values()):
        return st_load_file(path)
    mm = np.memmap(path, mode="r", dtype=np.uint8)
    out = {}
    for k, meta in header.items():
        b, e = meta["data_offsets"]
        dt = np.dtype(_ST_DTYPES[meta["dtype"]])
        if (
            b < 0
            or e < b
            or e - b != int(np.prod(meta["shape"])) * dt.itemsize
            or base + e > mm.size
        ):
            # Truncated/corrupt payload (e.g. a split killed mid-write):
            # the library loader raises the clear format error.
            return st_load_file(path)
        out[k] = mm[base + b : base + e].view(dt).reshape(meta["shape"])
    return out


def dequantize_tree_np(tree):
    """Host-side dequantize of every {"q8","s"} leaf-group in a pytree —
    for consumers that need real-valued host params (the streamed trainer;
    test oracles). The streaming executors dequantize ON DEVICE instead
    (runtime/executor._dequant_tree), after the int8 bytes cross the link."""
    import jax

    return jax.tree.map(
        lambda n: dequantize_np(n) if is_quantized_leaf(n) else n,
        tree,
        is_leaf=is_quantized_leaf,
    )


def load_layer(
    model_path: str,
    layer_name: str,
    manifest: dict | None = None,
    corrupt=None,
) -> dict[str, Any]:
    """Load one layer file into a native-layout parameter pytree (numpy;
    zero-copy mmap views where the file is already native layout). int8-
    compressed tensors come back as {"q8", "s"} leaf-groups, still int8 —
    dequantization happens on device, after the transfer.

    ``manifest``: an integrity manifest (integrity/manifest.py) — when it
    covers this layer, every stored tensor's checksum is verified and a
    mismatch raises the retryable ``ChecksumMismatch`` (re-reads heal
    page-cache corruption; the loader escalates persistence).
    ``corrupt``: chaos-only hook (``FaultInjector.corrupt_flat``) applied
    to the raw flat tensors BEFORE verification, so injected silent
    corruption is exactly what the checksums must catch."""
    path = os.path.join(model_path, f"{layer_name}{LAYER_FILE_SUFFIX}")
    # Verdict identity captured BEFORE the read, so a verify result can
    # only ever be recorded against the generation actually read.
    token = (
        integrity_manifest.verdict_token(model_path, path)
        if manifest is not None
        else None
    )
    flat = raw = _mmap_safetensors(path)
    # Re-stat AFTER the mmap: pre==post brackets the mapping, proving the
    # bytes belong to the generation the token names. On drift (the file
    # was atomically replaced mid-load) the cached verdict of the OLD
    # generation must not vouch for the NEW bytes — drop the token, which
    # forces a full verify of this load and records nothing.
    if token is not None and (
        integrity_manifest.verdict_token(model_path, path) != token
    ):
        token = None
    if corrupt is not None:
        flat = corrupt(flat)
    if manifest is not None:
        # Amortized hashing: a file generation is crc-verified ONCE, then
        # later sweeps reuse the cached clean verdict keyed by the file's
        # and the manifest's stat (any on-disk change invalidates). The
        # cache is bypassed whenever the chaos injector actually corrupted
        # this load (corrupt_flat returns a COPY then) — injected in-memory
        # corruption must be caught by a real checksum pass every time.
        injected = flat is not raw
        if injected or not integrity_manifest.verdict_cached(token):
            integrity_manifest.verify_flat(
                layer_name, flat, manifest, path=path
            )
            if not injected:
                integrity_manifest.record_verdict(token)
        # Per-layer PRECISION check, on every load (cheap — a key scan
        # plus header dtypes, independent of the crc verdict cache): the
        # file's actual storage-dtype kind must match what the manifest
        # declares for this layer. Catches a silently swapped file whose
        # precision disagrees with the mixed-precision plan the manifest
        # was written against — typed and structural, never retried.
        entry = manifest.get("layers", {}).get(layer_name) or {}
        want_kind = entry.get("dtype")
        if want_kind is not None:
            got_kind = flat_dtype_kind(flat)
            if got_kind != want_kind:
                raise integrity_manifest.PrecisionMismatch(
                    f"{path}: layer {layer_name!r} stores dtype kind "
                    f"{got_kind!r} but the integrity manifest declares "
                    f"{want_kind!r} — the file does not match the "
                    "precision the checkpoint was prepared at (audit "
                    "with the `verify` CLI subcommand)"
                )
    if not _is_native(flat.keys()):
        flat = hf_layer_to_native(layer_name, flat)
    if any(k.endswith((QUANT_SCALE_SUFFIX, QUANT4_SCALE_SUFFIX)) for k in flat):
        grouped: dict[str, Any] = {}
        for k, v in flat.items():
            if k.endswith((QUANT_SCALE_SUFFIX, QUANT4_SCALE_SUFFIX)):
                continue
            s8, s4 = k + QUANT_SCALE_SUFFIX, k + QUANT4_SCALE_SUFFIX
            if s4 in flat:
                grouped[k] = {"q4": v, "s": flat[s4]}
            elif s8 in flat:
                grouped[k] = {"q8": v, "s": flat[s8]}
            else:
                grouped[k] = v
        flat = grouped
    return native_to_pytree(layer_name, flat)


def _cast_flat_bf16(sd: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Cast every float tensor to bfloat16 — the SAME uniform cast rule
    ``split_into_layers(dtype='bfloat16')`` applies, so a plan's bf16
    layers are bit-identical to the uniform-bf16 baseline checkpoint."""
    if _BFLOAT16 is None:
        raise ImportError("dtype='bfloat16' requires ml_dtypes")
    return {
        k: np.asarray(v, dtype=_BFLOAT16) if is_float_like(v) else v
        for k, v in sd.items()
    }


def _encode_flat(sd: dict[str, np.ndarray], dtype: str) -> dict[str, np.ndarray]:
    """One layer's flat native tensors re-encoded at ``dtype`` — the
    per-layer primitive requantize_native applies uniformly or per a
    PrecisionPlan. Plan dtype 'bf16' aliases the storage name."""
    if dtype in ("bfloat16", "bf16"):
        return _cast_flat_bf16(sd)
    return _quantize_flat(sd, dtype)


def requantize_native(
    src_dir: str, out_dir: str, dtype: str = "int8", plan=None
) -> list[str]:
    """Re-encode an existing NATIVE per-layer checkpoint dir as int8
    (per-output-channel), int4 (group-wise packed), bfloat16 (cast only)
    — same conventions as ``split_into_layers(dtype=...)`` — or, with
    ``plan`` (a ``runtime.precisionplan.PrecisionPlan``), at a PER-LAYER
    dtype mix, without going back through the HF source. A plan must
    cover every layer file (a partial plan raises — silently defaulting
    a layer's precision is exactly the drift the plan artifact exists to
    prevent); the plan is embedded in the output dir
    (``precision_plan.json``) and the fresh integrity manifest records
    each layer's dtype kind, so the `verify` audit and the load path can
    both detect a plan/file mismatch as a typed error. Copies aux files
    (config.json, tokenizer) alongside. Returns the layer names
    converted."""
    if plan is None and dtype not in ("int8", "int4", "bfloat16"):
        raise ValueError(f"requantize_native: unsupported dtype {dtype!r}")
    if plan is not None:
        # Coverage validated BOTH ways BEFORE the first byte is written:
        # a drifted plan must fail up front, not strand a half-quantized
        # output dir (layer files but no manifest, no embedded plan —
        # which would later load unverified) after hours of work.
        on_disk = {
            fn[: -len(LAYER_FILE_SUFFIX)]
            for fn in os.listdir(src_dir)
            if fn.endswith(LAYER_FILE_SUFFIX)
        }
        missing = on_disk - set(plan.dtypes)
        extra = set(plan.dtypes) - on_disk
        if missing or extra:
            raise ValueError(
                f"precision plan and {src_dir} drifted: layers on disk "
                f"with no plan entry {sorted(missing)}; planned layers "
                f"with no file {sorted(extra)}"
            )
    os.makedirs(out_dir, exist_ok=True)
    # Function-level import (checkpoint is imported by precisionplan at
    # module scope; by requantize time both are importable).
    from flexible_llm_sharding_tpu.runtime.precisionplan import (
        PLAN_NAME as _PLAN_NAME,
    )

    done = []
    manifest_layers: dict[str, dict] = {}
    for fn in sorted(os.listdir(src_dir)):
        src = os.path.join(src_dir, fn)
        if not fn.endswith(LAYER_FILE_SUFFIX):
            # The source's integrity manifest must NOT ride along — its
            # checksums describe the float tensors, not the re-encoded
            # ones; a fresh manifest is written below. A source-embedded
            # precision plan is stale for the same reason.
            if (
                os.path.isfile(src)
                and fn != NATIVE_LAYOUT_MARKER
                and fn != integrity_manifest.MANIFEST_NAME
                and fn != _PLAN_NAME
            ):
                shutil.copy(src, os.path.join(out_dir, fn))
            continue
        layer_name = fn[: -len(LAYER_FILE_SUFFIX)]
        flat = _mmap_safetensors(src)
        if not _is_native(flat.keys()):
            raise ValueError(f"{fn}: not native layout (run split_into_layers)")
        if any(
            k.endswith((QUANT_SCALE_SUFFIX, QUANT4_SCALE_SUFFIX)) for k in flat
        ):
            # Re-quantizing a quantized dir would treat the 2-D fp32 scale
            # tensors as kernels (int4's ::scale4 in particular) and emit
            # silently-corrupt files; demand the original float checkpoint.
            raise ValueError(
                f"{fn}: source is already quantized; requantize from the "
                "original float checkpoint"
            )
        layer_dtype = dtype if plan is None else plan.dtype_for(layer_name)
        qd = _encode_flat(flat, layer_dtype)
        stored = {k: np.ascontiguousarray(v) for k, v in qd.items()}
        st_save_file(stored, os.path.join(out_dir, fn))
        manifest_layers[layer_name] = integrity_manifest.layer_entry(
            stored, fn
        )
        done.append(layer_name)
    if plan is not None:
        plan.save(out_dir)
    with open(os.path.join(out_dir, NATIVE_LAYOUT_MARKER), "w") as f:
        json.dump(
            {
                "layout": "native",
                "dtype": "mixed" if plan is not None else dtype,
                "layers": done,
            },
            f,
        )
    integrity_manifest.write_manifest(out_dir, manifest_layers)
    return done


def save_params(params: dict[str, Any], out_dir: str, cfg: LlamaConfig) -> None:
    """Save a full in-memory params pytree as per-layer native files + config.json
    (test/synthetic-model helper; the offline path is split_into_layers)."""
    os.makedirs(out_dir, exist_ok=True)

    def flatten(tree: dict[str, Any], prefix: str = "") -> Iterator[tuple[str, np.ndarray]]:
        for k, v in tree.items():
            name = f"{prefix}.{k}" if prefix else k
            if isinstance(v, dict):
                yield from flatten(v, name)
            else:
                # np.asarray over a jax array can yield a non-contiguous view;
                # safetensors serializes the raw buffer ignoring strides, so
                # contiguity is mandatory here.
                yield name, np.ascontiguousarray(np.asarray(v))

    manifest_layers: dict[str, dict] = {}

    def _save(layer_name: str, tree: dict[str, Any]) -> None:
        flat = dict(flatten(tree))
        st_save_file(flat, os.path.join(out_dir, f"{layer_name}.safetensors"))
        manifest_layers[layer_name] = integrity_manifest.layer_entry(
            flat, f"{layer_name}.safetensors"
        )

    _save("model.embed_tokens", params["embed"])
    for i, layer in enumerate(params["layers"]):
        _save(f"model.layers.{i}", layer)
    _save("model.norm", params["norm"])
    if "lm_head" in params and params["lm_head"]:
        _save("lm_head", params["lm_head"])
    integrity_manifest.write_manifest(out_dir, manifest_layers)
    import dataclasses as _dc

    # EVERY dataclass field serializes by name (tuples become json lists;
    # from_hf_config's native path coerces the known tuple fields back).
    # A hand-maintained field list here silently dropped newly-added fields
    # (an MLA config round-tripped to the 128/64 head-dim defaults) — the
    # asdict dump cannot drift.
    hf_cfg = {
        # Marks a config this framework wrote itself: every native field is
        # explicit and from_hf_config round-trips them all by name. Foreign
        # configs (no marker) get the per-family stray-key defence instead.
        "fls_native": True,
        "use_sliding_window": cfg.sliding_window is not None,  # qwen2 gate
        **_dc.asdict(cfg),
    }
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_cfg, f)
