"""Plain float32 reference for ``mimo_v2_flash`` (MiMo-V2-Flash): full and
sliding-window attention layers of different shapes in one model (64 query
heads over 4 KV heads in a full layer, over 8 in a window layer; qk width 192,
v width 128, no latent compression), rotary on the first
``int(head_dim * partial_rotary_factor)`` dims (half-rotation) at the layer
kind's own base, values scaled by ``attention_value_scale``, a learned sink
logit per head in the window layers' softmax denominator, a dense SwiGLU first
layer, and the sigmoid / correction-bias top-k router over experts with no
shared one, of which the configuration HOLDS a share: the router scores its
whole width, and the layer's result is the held experts' part.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching tricks. It imports nothing of the program and
gets its weights from ``weights.layer_tensors`` (the seed alone). What is the
same for every family (the sequence layouts with their masks, the weights'
lower-precision controls, RMSNorm, SwiGLU, rotary tables, the router) is
imported from ``benchmark/reference.py``. Attention runs one KV head's query
group at a time, so that a [T, T] score block per query head is all that
sits beside one layer's float32 weights at 3.6k tokens.

``leave_out`` names parts of the mathematics to drop, for the controls that
show the comparison sees each: ``sink``, ``window``, ``value_scale``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as base
from benchmark.families.mimo_v2_flash import weights

HIGHEST = base.HIGHEST
scoring_sequence = base.scoring_sequence
causal_sequence = base.causal_sequence


def attention(m, p, x, positions, mask, window: bool, leave_out=()):
    nq, nkv, hd, vd = weights.attn_shape(m, window)
    t = x.shape[0]
    q = base._mm(x, p["wq"]).reshape(t, nq, hd)
    k = base._mm(x, p["wk"]).reshape(t, nkv, hd)
    v = base._mm(x, p["wv"]).reshape(t, nkv, vd)
    rd = int(hd * float(m["partial_rotary_factor"]))
    theta = float(m["swa_rope_theta"] if window else m["rope_theta"])
    cos, sin = base._rope_cos_sin(positions, rd, theta)

    def rot(a):
        r = a[..., :rd] * cos[:, None, :] + base._rotate_half(a[..., :rd]) * sin[:, None, :]
        return jnp.concatenate([r, a[..., rd:]], axis=-1)

    q, k = rot(q), rot(k)
    if "value_scale" not in leave_out:
        v = v * float(m["attention_value_scale"])
    if window and "window" not in leave_out:
        dist = positions[:, None] - positions[None, :]
        mask = mask & (dist < int(m["sliding_window"]))
    sink = p.get("sink")
    if "sink" in leave_out:
        sink = None
    g = nq // nkv

    def one_kv_head(args):
        qg, kh, vh, sg = args  # [T, g, hd], [T, hd], [T, vd], [g]
        s = jnp.einsum("qgd,kd->gqk", qg, kh, precision=HIGHEST) * hd ** -0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        if sink is not None:  # one more column in the denominator, no value
            col = jnp.broadcast_to(sg[:, None, None], (g, t, 1))
            a = jax.nn.softmax(jnp.concatenate([s, col], axis=-1), axis=-1)[..., :-1]
        else:
            a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqk,kd->qgd", a, vh, precision=HIGHEST)  # [T, g, vd]

    sinks = (sink if sink is not None else jnp.zeros((nq,), jnp.float32)).reshape(nkv, g)
    o = jax.lax.map(
        one_kv_head,
        (q.reshape(t, nkv, g, hd).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), sinks),
    )  # [nkv, T, g, vd]
    return base._mm(o.swapaxes(0, 1).reshape(t, nq * vd), p["wo"])


def _router_m(m):
    """The router's settings under the names ``benchmark.reference.choose``
    reads: it scores the whole width, whatever share is held."""
    rsf = m.get("routed_scaling_factor")
    return {**m, "n_routed_experts": weights.router_width(m),
            "routed_scaling_factor": 1.0 if rsf is None else rsf}


def moe(m, p, x):
    """The held experts' part of the layer's result: the router's combine
    weights over its whole width, the held ids' columns of them."""
    held = weights.held_experts(m)
    combine = base.route(_router_m(m), p, x)[:, held.start:held.stop]  # [T, E held]

    def one(acc, ew):
        gate, up, down, c = ew
        return acc + c[:, None] * base._swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], combine.T))
    return out


@partial(jax.jit, static_argnums=(0, 5, 6, 7))
def _layer(mkey, p, x, positions, mask, window, quant, leave_out):
    m = dict(mkey)
    eps = float(m["layernorm_epsilon"])
    h = base.rms_norm(x, p["input_layernorm"]["scale"], eps)
    x = base._act(x + attention(m, p["attn"], h, positions, mask, window, leave_out), quant)
    hmid = base.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if "router" in p["mlp"]:
        y, chosen = moe(m, p["mlp"], hmid), base.choose(_router_m(m), p["mlp"], hmid)[1]
    else:
        y, chosen = base._swiglu(hmid, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"]), None
    return base._act(x + y, quant), chosen


@partial(jax.jit, static_argnums=(0,))
def _head(mkey, norm, head, x_rows):
    m = dict(mkey)
    return base._mm(base.rms_norm(x_rows, norm["scale"], float(m["layernorm_epsilon"])),
                    head["kernel"])


def _mkey(model: dict):
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
            "swa_v_head_dim", "partial_rotary_factor", "rope_theta", "swa_rope_theta",
            "sliding_window", "attention_value_scale", "layernorm_epsilon",
            "n_routed_experts", "ep_size", "ep_rank", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, model[k]) for k in keep if model.get(k) is not None)


def layer_weights(model: dict, seed: int, name: str, quant=None) -> dict:
    flat = weights.layer_tensors(model, seed, name)
    return weights.unflatten({k: base._prep(a, quant) for k, a in flat.items()})


def forward_rows(model: dict, seed: int, seqs: list[dict], quant=None, taps: list | None = None,
                 leave_out=()) -> list[np.ndarray]:
    """As ``benchmark.reference.forward_rows``: ``seqs`` are dicts with
    ``ids`` [T], ``positions`` [T], ``mask`` [T, T] and ``rows``; one float32
    [len(rows), vocab held] logits array per sequence comes back. ``taps``
    gets, per expert layer, the experts chosen (of the router's whole width)
    at the wanted rows. ``leave_out``: see the module's docstring."""
    mkey, leave_out = _mkey(model), tuple(sorted(leave_out))
    names = weights.layer_names(model)
    emb = layer_weights(model, seed, names[0], quant)["embedding"]
    xs = [emb[jnp.asarray(s["ids"])] for s in seqs]
    del emb
    pos = [jnp.asarray(s["positions"], jnp.int32) for s in seqs]
    masks = [jnp.asarray(s["mask"]) for s in seqs]
    for i, name in enumerate(names[1:-2]):
        p = layer_weights(model, seed, name, quant)
        window = weights.is_window_layer(model, i)
        outs = [_layer(mkey, p, x, pp, mk, window, quant, leave_out)
                for x, pp, mk in zip(xs, pos, masks)]
        xs = [o[0] for o in outs]
        if taps is not None and outs[0][1] is not None:
            taps.append([np.sort(np.asarray(o[1])[s["rows"]], -1) for o, s in zip(outs, seqs)])
        del p, outs
    norm = layer_weights(model, seed, names[-2], quant)
    head = layer_weights(model, seed, names[-1], quant)
    return [np.asarray(_head(mkey, norm, head, x[jnp.asarray(s["rows"], jnp.int32)]))
            for x, s in zip(xs, seqs)]
