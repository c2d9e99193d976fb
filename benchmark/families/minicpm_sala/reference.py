"""Plain float32 reference for ``minicpm_sala`` (MiniCPM-SALA): 32 layers of
two kinds in the order ``mixer_types`` lists, with MiniCPM's muP scalings.

With x the residual stream, L layers and ``r = scale_depth / sqrt(L)``:

- embedding: ``x = scale_emb * E[ids]``;
- every layer: ``x = x + r * Mixer(RMSNorm(x))``, then ``x = x + r *
  W_down(silu(W_gate h) * W_up h)`` with ``h = RMSNorm(x)``;
- ``lightning-attn`` mixer, head n of H at width d: q, k, v projections; a
  per-head RMSNorm with a learned scale on q and on k; rotary over the whole
  head at ``rope_theta``; the decayed linear recurrence ``S_t = exp(-s) S_{t-1}
  + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``, computed here as the masked
  quadratic form ``o_t = sum_{j <= t} exp(-s (t - j)) (q_t . k_j) v_j /
  sqrt(d)`` so that it shares not even the algorithm with the program; a
  per-head RMSNorm of o with a learned scale; ``o * sigmoid(W_g h)``; ``W_o``;
- ``minicpm4`` mixer: grouped-query causal softmax attention at 1/sqrt(d) with
  the same q/k norm and NO rotary, ``o * sigmoid(W_g h)``, ``W_o``. Dense for
  every length under ``dense_len`` (the published model switches its learned
  block-sparse selection on only from there): the length is asserted;
- head: ``logits = W_head (RMSNorm(x) / (hidden_size / dim_model_base))``.

What the catalog's ``config`` does not carry (the decay's formula, the
norm-before-gate order, ``dense_len``) is listed with its reason under
``assumed`` in ``benchmark/configs/minicpm-sala.json``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no chunking. It imports nothing of the program and gets its
weights from ``weights.layer_tensors`` (the seed alone). What is the same for
every family (the sequence layouts with their masks, the weights'
lower-precision controls, RMSNorm, SwiGLU, rotary tables) is imported from
``benchmark/reference.py``. One head (linear) or one KV head's query group
(softmax) at a time, so that a [T, T] block per query head is all that sits
beside one layer's float32 weights at 3.6k tokens.

``leave_out`` names parts of the mathematics to drop or change, for the
controls that show the comparison sees each: ``decay``, ``gate``,
``output_norm``, ``qk_norm``, ``mup`` (the three scalings), ``nope`` (rotary
applied in the softmax layers too).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as base
from benchmark.families.minicpm_sala import weights

HIGHEST = base.HIGHEST
scoring_sequence = base.scoring_sequence
causal_sequence = base.causal_sequence
DENSE_LEN = 8192  # minicpm4 layers are dense causal attention below it


def _rope(m, a, positions):
    cos, sin = base._rope_cos_sin(positions, a.shape[-1], float(m["rope_theta"]))
    return a * cos[:, None, :] + base._rotate_half(a) * sin[:, None, :]


def _qkv(m, p, x, positions, linear: bool, leave_out):
    nq, nkv, hd = weights.attn_shape(m, linear)
    t = x.shape[0]
    q = base._mm(x, p["wq"]).reshape(t, nq, hd)
    k = base._mm(x, p["wk"]).reshape(t, nkv, hd)
    v = base._mm(x, p["wv"]).reshape(t, nkv, hd)
    if "q_norm" in p and "qk_norm" not in leave_out:
        eps = float(m["rms_norm_eps"])
        q, k = base.rms_norm(q, p["q_norm"], eps), base.rms_norm(k, p["k_norm"], eps)
    rope = m["lightning_use_rope"] if linear else (m["attn_use_rope"] or "nope" in leave_out)
    if rope:
        q, k = _rope(m, q, positions), _rope(m, k, positions)
    return q, k, v


def _gate_out(p, o, x, leave_out):
    """o [T, heads, hd] -> gated, flat, through W_o."""
    o = o.reshape(o.shape[0], -1)
    if "wg" in p and "gate" not in leave_out:
        o = o * jax.nn.sigmoid(base._mm(x, p["wg"]))
    return base._mm(o, p["wo"])


def linear_mixer(m, p, x, positions, mask, s, leave_out=()):
    """``s`` float32 [heads]: the layer's decay rates. The scoring layout's
    mask says who continues whom (a suffix token the prefix and its own
    suffix); the positions give the distance the decay runs over."""
    q, k, v = _qkv(m, p, x, positions, True, leave_out)
    hd = q.shape[-1]
    dist = jnp.where(mask, positions[:, None] - positions[None, :], 0).astype(jnp.float32)
    if "decay" in leave_out:
        s = jnp.zeros_like(s)

    def one_head(args):
        qh, kh, vh, sh = args  # [T, hd] x 3, scalar
        w = jnp.where(mask, jnp.exp(-sh * dist), 0.0)
        a = jnp.einsum("qd,kd->qk", qh, kh, precision=HIGHEST) * w
        return jnp.einsum("qk,kd->qd", a, vh, precision=HIGHEST) * hd ** -0.5

    o = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), s))
    o = o.swapaxes(0, 1)  # [T, heads, hd]
    if "o_norm" in p and "output_norm" not in leave_out:
        o = base.rms_norm(o, p["o_norm"], float(m["rms_norm_eps"]))
    return _gate_out(p, o, x, leave_out)


def softmax_mixer(m, p, x, positions, mask, leave_out=()):
    q, k, v = _qkv(m, p, x, positions, False, leave_out)
    t, nq, hd = q.shape
    nkv = k.shape[1]
    g = nq // nkv

    def one_kv_head(args):
        qg, kh, vh = args  # [T, g, hd], [T, hd], [T, hd]
        sc = jnp.einsum("qgd,kd->gqk", qg, kh, precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", a, vh, precision=HIGHEST)

    o = jax.lax.map(
        one_kv_head, (q.reshape(t, nkv, g, hd).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))
    )  # [nkv, T, g, hd]
    return _gate_out(p, o.swapaxes(0, 1).reshape(t, nq, hd), x, leave_out)


def residual_scale(m, leave_out=()) -> float:
    if "mup" in leave_out:
        return 1.0
    return float(m["scale_depth"]) / math.sqrt(int(m["num_hidden_layers"]))


@partial(jax.jit, static_argnums=(0, 6, 7, 8))
def _layer(mkey, p, x, positions, mask, s, linear, quant, leave_out):
    m = dict(mkey)
    eps, r = float(m["rms_norm_eps"]), residual_scale(m, leave_out)
    h = base.rms_norm(x, p["input_layernorm"]["scale"], eps)
    if linear:
        y = linear_mixer(m, p["attn"], h, positions, mask, s, leave_out)
    else:
        y = softmax_mixer(m, p["attn"], h, positions, mask, leave_out)
    x = base._act(x + r * y, quant)
    h = base.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    y = base._swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return base._act(x + r * y, quant)


@partial(jax.jit, static_argnums=(0, 4))
def _head(mkey, norm, head, x_rows, leave_out):
    m = dict(mkey)
    h = base.rms_norm(x_rows, norm["scale"], float(m["rms_norm_eps"]))
    if "mup" not in leave_out:
        h = h / (int(m["hidden_size"]) / int(m["dim_model_base"]))
    return base._mm(h, head["kernel"])


def _mkey(model: dict):
    keep = ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "lightning_nh", "lightning_nkv", "lightning_head_dim",
            "lightning_use_rope", "attn_use_rope", "rope_theta", "rms_norm_eps",
            "scale_emb", "scale_depth", "dim_model_base")
    return tuple((k, model[k]) for k in keep)


def layer_weights(model: dict, seed: int, name: str, quant=None) -> dict:
    flat = weights.layer_tensors(model, seed, name)
    return weights.unflatten({k: base._prep(a, quant) for k, a in flat.items()})


def forward_rows(model: dict, seed: int, seqs: list[dict], quant=None, taps: list | None = None,
                 leave_out=()) -> list[np.ndarray]:
    """As ``benchmark.reference.forward_rows``: ``seqs`` are dicts with
    ``ids`` [T], ``positions`` [T], ``mask`` [T, T] and ``rows``; one float32
    [len(rows), vocab] logits array per sequence comes back. ``taps`` is the
    drivers' interface and gets nothing (no expert layer). ``leave_out``: see
    the module's docstring."""
    if model["lightning_scale"] != "1/sqrt(d)":
        raise NotImplementedError(f"lightning_scale {model['lightning_scale']!r}")
    longest = max(int(np.max(s["positions"])) + 1 for s in seqs)
    assert longest < DENSE_LEN, f"{longest} tokens: the sparse branch is not in this reference"
    mkey, leave_out = _mkey(model), tuple(sorted(leave_out))
    names = weights.layer_names(model)
    emb = layer_weights(model, seed, names[0], quant)["embedding"]
    scale_emb = 1.0 if "mup" in leave_out else float(model["scale_emb"])
    xs = [scale_emb * emb[jnp.asarray(s["ids"])] for s in seqs]
    del emb
    pos = [jnp.asarray(s["positions"], jnp.int32) for s in seqs]
    masks = [jnp.asarray(s["mask"]) for s in seqs]
    for i, name in enumerate(names[1:-2]):
        p = layer_weights(model, seed, name, quant)
        linear = weights.is_linear_layer(model, i)
        s = -jnp.asarray(weights.log_decay(model, i))
        xs = [_layer(mkey, p, x, pp, mk, s, linear, quant, leave_out)
              for x, pp, mk in zip(xs, pos, masks)]
        del p
    norm = layer_weights(model, seed, names[-2], quant)
    head = layer_weights(model, seed, names[-1], quant)
    return [np.asarray(_head(mkey, norm, head, x[jnp.asarray(s["rows"], jnp.int32)], leave_out))
            for x, s in zip(xs, seqs)]
