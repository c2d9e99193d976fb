"""Plain float32 reference for ``deepseek_v3`` (HF ``DeepseekV3`` modelling):
MLA attention (dense ``q_proj``, compressed KV with one shared rope key,
interleaved rope), dense SwiGLU MLP, the sigmoid / correction-bias / group-
limited top-k router with renormalised scaled weights, routed + shared
experts, final RMSNorm and head.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching tricks. It imports nothing of the program and
gets its weights from ``weights.layer_tensors`` (the seed alone).

One sequence is a flat row of tokens with explicit ``positions`` and an
explicit boolean ``mask`` [T, T] (True = may attend), so the same function
serves a plain causal sequence and the scoring layout
``[prefix | suffix 1 | suffix 2 | ...]`` in which every suffix sees the
prefix and itself. It runs layer by layer over all sequences, so the
float32 copy of one layer is all that ever sits on the device.

``quant`` puts the weights through a lower precision first (``int8`` and
``fp8`` e4m3: symmetric, one scale per output channel; ``bf16_act``: weights as
they are, activations rounded to bfloat16 after every matmul — the program's
own precision, used only to size the rehearsal). That is the control: the
reference in the program's place, one precision step down.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_cos_sin(positions, dim, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _rope(x, cos, sin, interleave):
    """x [T, H, dr]. HF ``apply_rotary_pos_emb_interleave``: pairs
    (x0,x1),(x2,x3).. are first laid out as [x0,x2,..,x1,x3,..]."""
    if interleave:
        t, h, d = x.shape
        x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    return x * cos[:, None, :] + _rotate_half(x) * sin[:, None, :]


def attention(m, p, x, positions, mask):
    h = int(m["num_attention_heads"])
    dn, dr = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"])
    dv, kvr = int(m["v_head_dim"]), int(m["kv_lora_rank"])
    eps = float(m["rms_norm_eps"])
    t = x.shape[0]
    q = _mm(x, p["wq"]).reshape(t, h, dn + dr)
    ckv = _mm(x, p["kv_a"])
    c_kv, k_rot = ckv[:, :kvr], ckv[:, kvr:]
    kv = _mm(rms_norm(c_kv, p["kv_a_norm"], eps), p["kv_b"]).reshape(t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    cos, sin = _rope_cos_sin(positions, dr, float(m["rope_theta"]))
    il = bool(m.get("rope_interleave", True))
    q_rot = _rope(q[..., dn:], cos, sin, il)
    k_rot = _rope(k_rot[:, None, :], cos, sin, il)
    qf = jnp.concatenate([q[..., :dn], q_rot], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rot, (t, h, dr))], axis=-1)
    s = jnp.einsum("qhd,khd->hqk", qf, kf, precision=HIGHEST) * (dn + dr) ** -0.5
    s = jnp.where(mask[None, :, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST).reshape(t, h * dv)
    return _mm(o, p["wo"])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def choose(m, p, x):
    """(scores [T, E], the chosen experts' indices [T, k])."""
    e, k = int(m["n_routed_experts"]), int(m["num_experts_per_tok"])
    g, kg = int(m.get("n_group", 1)), int(m.get("topk_group", 1))
    scores = jax.nn.sigmoid(_mm(x.astype(jnp.float32), p["router"]))
    choice = scores + p["correction_bias"]
    if g > 1:
        grouped = choice.reshape(-1, g, e // g)
        group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
        keep = jax.lax.top_k(group_scores, kg)[1]
        gmask = jnp.zeros_like(group_scores).at[
            jnp.arange(group_scores.shape[0])[:, None], keep
        ].set(1.0)
        choice = jnp.where(jnp.repeat(gmask, e // g, axis=-1) > 0, choice, 0.0)
    return scores, jax.lax.top_k(choice, k)[1]


def route(m, p, x):
    """[T, E] combine weights (zero off the chosen experts)."""
    scores, idx = choose(m, p, x)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if m.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(m.get("routed_scaling_factor", 1.0))
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].add(w)


def moe(m, p, x):
    combine = route(m, p, x)  # [T, E]

    def one(acc, ew):
        gate, up, down, c = ew
        return acc + c[:, None] * _swiglu(x, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], combine.T)
    )
    if "shared_gate" in p:
        routed = routed + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return routed


def _act(x, quant):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if quant == "bf16_act" else x


@partial(jax.jit, static_argnums=(0, 5))
def _layer(mkey, p, x, positions, mask, quant):
    m = dict(mkey)
    eps = float(m["rms_norm_eps"])
    x = _act(x + attention(m, p["attn"], rms_norm(x, p["input_layernorm"]["scale"], eps),
                           positions, mask), quant)
    hmid = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if "router" in p["mlp"]:
        y, chosen = moe(m, p["mlp"], hmid), choose(m, p["mlp"], hmid)[1]
    else:
        y, chosen = _swiglu(hmid, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"]), None
    return _act(x + y, quant), chosen


@partial(jax.jit, static_argnums=(0,))
def _head(mkey, norm, head, x_rows):
    m = dict(mkey)
    return _mm(rms_norm(x_rows, norm["scale"], float(m["rms_norm_eps"])), head["kernel"])


def _mkey(model: dict):
    keep = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "rms_norm_eps", "rope_theta", "rope_interleave",
            "n_routed_experts", "num_experts_per_tok", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, model[k]) for k in keep if k in model and model[k] is not None)


@partial(jax.jit, static_argnums=(1,))
def _prep(w, quant):
    """bf16 weight -> float32, through the control's precision if asked."""
    w = w.astype(jnp.float32)
    if w.ndim < 2 or quant in (None, "bf16_act"):
        return w
    if quant == "fp8":
        # e4m3 with a scale per output channel, rounded by arithmetic: four
        # significant bits (the TPU compiler folds a float8 cast pair away,
        # which read 0.0 on the chip: PERF.md Findings, PR 24).
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        mant, expo = jnp.frexp(w / s)
        q = jnp.ldexp(jnp.round(mant * 16.0) / 16.0, expo)
        return jnp.clip(q, -448.0, 448.0) * s
    if quant == "int8":
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(w / s).clip(-127, 127) * s
    raise ValueError(quant)


def layer_weights(model: dict, seed: int, name: str, quant=None) -> dict:
    from benchmark import weights

    flat = weights.layer_tensors(model, seed, name)
    return weights.unflatten({k: _prep(a, quant) for k, a in flat.items()})


def forward_rows(model: dict, seed: int, seqs: list[dict], quant=None, taps: list | None = None
                 ) -> list[np.ndarray]:
    """``seqs``: dicts with ``ids`` [T], ``positions`` [T], ``mask`` [T, T]
    (numpy, all the same T) and ``rows`` (indices whose logits are wanted).
    Returns one float32 [len(rows), vocab] logits array per sequence. ``taps``
    (a list, for a look at routing) gets, per expert layer, the experts chosen
    at the wanted rows of each sequence: [sequences][rows, k]."""
    from benchmark import weights

    mkey = _mkey(model)
    names = weights.layer_names(model)
    emb = layer_weights(model, seed, names[0], quant)["embedding"]
    xs = [emb[jnp.asarray(s["ids"])] for s in seqs]
    del emb
    pos = [jnp.asarray(s["positions"], jnp.int32) for s in seqs]
    masks = [jnp.asarray(s["mask"]) for s in seqs]
    for name in names[1:-2]:
        p = layer_weights(model, seed, name, quant)
        outs = [_layer(mkey, p, x, pp, mk, quant) for x, pp, mk in zip(xs, pos, masks)]
        xs = [o[0] for o in outs]
        if taps is not None and outs[0][1] is not None:
            taps.append([np.sort(np.asarray(o[1])[s["rows"]], -1) for o, s in zip(outs, seqs)])
        del p, outs
    norm = layer_weights(model, seed, names[-2], quant)
    head = layer_weights(model, seed, names[-1], quant)
    out = [
        np.asarray(_head(mkey, norm, head, x[jnp.asarray(s["rows"], jnp.int32)]))
        for x, s in zip(xs, seqs)
    ]
    return out


def scoring_sequence(prefix_ids, suffix_ids_list, pad_to: int, pad_id: int = 0) -> dict:
    """The layout the scoring path computes: the prefix, then each suffix
    continuing from the prefix's end and blind to the other suffixes. Rows
    wanted: each suffix's last token."""
    lp = len(prefix_ids)
    ids = list(prefix_ids)
    positions = list(range(lp))
    seg = [0] * lp
    rows = []
    for j, s in enumerate(suffix_ids_list, 1):
        ids += list(s)
        positions += list(range(lp, lp + len(s)))
        seg += [j] * len(s)
        rows.append(len(ids) - 1)
    n = len(ids)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens over pad_to={pad_to}")
    ids += [pad_id] * (pad_to - n)
    positions += [0] * (pad_to - n)
    seg += [-1] * (pad_to - n)
    seg = np.asarray(seg)
    i = np.arange(pad_to)
    causal = i[None, :] <= i[:, None]
    same = seg[:, None] == seg[None, :]
    to_prefix = (seg[None, :] == 0) & (seg[:, None] > 0)
    mask = (causal & same) | to_prefix
    mask |= np.eye(pad_to, dtype=bool)  # padding rows attend to themselves
    return {"ids": np.asarray(ids, np.int32), "positions": np.asarray(positions, np.int32),
            "mask": mask, "rows": rows}


def causal_sequence(ids, rows, pad_to: int, pad_id: int = 0) -> dict:
    """A plain causal sequence (serving: prefix + suffix + served tokens)."""
    n = len(ids)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens over pad_to={pad_to}")
    i = np.arange(pad_to)
    return {
        "ids": np.asarray(list(ids) + [pad_id] * (pad_to - n), np.int32),
        "positions": i.astype(np.int32),
        "mask": i[None, :] <= i[:, None],
        "rows": list(rows),
    }
