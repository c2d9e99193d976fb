"""Plain float32 reference for ``glm5_next_text`` (GLM-5.3-Flash): layers of two
mixers in the order ``layer_types`` lists, a dense or an expert MLP as
``mlp_layer_types`` lists, around every sublayer the four-stream residual
(mHC). Per token the residual is ``X`` [4, D]; ``N`` is RMSNorm with a learned
scale.

- embedding: ``X[i] = E[id]`` for every stream i; head: ``W_head N(sum_i X[i])``;
- around a sublayer F with weights ``phi`` [4 D, 24], ``b`` [24], ``a`` [3]:
  ``xt = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``, ``[p, q, R] = split(xt phi,
  4, 4, 16)``, ``H_pre = sigmoid(a0 p + b)``, ``H_post = 2 sigmoid(a1 q + b)``,
  ``H_res = SK(exp(a2 R + b))`` with SK ``hc_sinkhorn_iters`` rounds of (rows
  over their sum + hc_eps, then columns); ``u = sum_i H_pre[i] X[i]``, ``y =
  F(N(u))``, ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``;
- ``linear_attention`` (KDA), input x: ``q, k, v = silu(conv4(W x))`` (causal
  depthwise, the last tap on the token itself), q and k L2-normalised a head,
  q over sqrt(d); ``g = gate_lower_bound * sigmoid(exp(A_log) * (W_f2 W_f1 x +
  dt_bias))``; ``beta = sigmoid(W_b x)``; ``S_t = (I - beta k k^T) Diag(exp(g))
  S_{t-1} + beta k v^T``, ``o_t = S_t^T q_t``; ``W_o [N_head(o) * sigmoid(W_g2
  W_g1 x)]``. Computed here ONE TOKEN AT A TIME (a ``lax.scan`` over the rows,
  the state and the convolution's last three inputs in the carry), so that it
  shares not even the algorithm with the program's chunked forms. The scoring
  layout's mask says whom a token continues: its predecessor is the last
  earlier token it may attend to (the token before it, the prefix's last token
  for a suffix's first, none for padding), so one scan serves any layout in
  which at most one token is continued more than once;
- ``deepseek_sparse_attention``: ``q = W_qb N(W_qa x)``, ``c = N(W_kva x)``,
  ``[k; v] = W_kvb c``, causal softmax at 1/sqrt(qk dim) over ALL the keys the
  mask allows, no rotary. Exact while a prompt has at most ``index_topk``
  tokens (top-k of fewer keys is all of them): the length is asserted;
- MLP: dense SwiGLU, or sigmoid router (top-8 of score + bias, weights
  normalised, x ``routed_scaling_factor``) over the HELD experts' columns plus
  the shared expert; every SwiGLU ``down(silu(min(gate, L)) * clip(up, -L,
  L))``.

What the catalog's ``config`` does not carry is listed under ``assumed`` in
``benchmark/configs/glm-5.3-flash.json``. Departures from the published
model: the indexer (above), the vision tower and the multi-token-prediction
layer (no part in scoring).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no chunking. It imports nothing of the program and gets its
weights from ``weights.layer_tensors`` (the seed alone). One head at a time in
the latent layers, so that a [T, T] block is all that sits beside one layer's
float32 weights.

``leave_out`` names parts of the mathematics to drop or change (``PARTS``),
for the controls that show the comparison sees each: ``mhc`` (every stream
gets the plain residual ``X[i] + F(N(mean_j X[j]))``), ``sinkhorn`` (1 round
for 20), ``decay`` (g = 0), ``beta`` (beta = 1), ``conv`` (the three earlier
taps dropped), ``clamp``, ``nope`` (rotary at base 10000 added to the latent
layers' q and k).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as base
from benchmark.families.glm5_next_text import weights

HIGHEST = base.HIGHEST
PARTS = ("mhc", "sinkhorn", "decay", "beta", "conv", "clamp", "nope")
scoring_sequence = base.scoring_sequence
causal_sequence = base.causal_sequence
_mm = base._mm


def swiglu(m, x, gate, up, down, leave_out=()):
    g, u = _mm(x, gate), _mm(x, up)
    lim = m.get("swiglu_limit")
    if lim is not None and "clamp" not in leave_out:
        g, u = jnp.minimum(g, float(lim)), jnp.clip(u, -float(lim), float(lim))
    return _mm(jax.nn.silu(g) * u, down)


def sinkhorn(mat, iters: int, eps: float):
    """mat [T, n, n] positive."""
    for _ in range(iters):
        mat = mat / (mat.sum(-1, keepdims=True) + eps)
        mat = mat / (mat.sum(-2, keepdims=True) + eps)
    return mat


def hc_mixes(m, hc, x, leave_out=()):
    """x [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    n, eps = int(m["hc_mult"]), float(m["hc_eps"])
    flat = x.reshape(x.shape[0], -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    z = _mm(xt, hc["phi"])
    a, b = hc["a"], hc["b"]
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    iters = 1 if "sinkhorn" in leave_out else int(m["hc_sinkhorn_iters"])
    h_res = sinkhorn(jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n), iters, eps)
    return h_pre, h_post, h_res


def predecessors(mask: np.ndarray) -> tuple[np.ndarray, np.int32]:
    """(int32 [T]: the last earlier token each token may attend to, -1 for
    none (a sequence's first token, padding); the one token that is continued
    more than once (the prefix's last), -2 for none)."""
    t = mask.shape[0]
    earlier = np.tril(mask, -1)
    pred = np.where(earlier, np.arange(t)[None, :], -1).max(axis=1).astype(np.int32)
    forks = np.flatnonzero(np.bincount(pred[pred >= 0], minlength=t) > 1)
    assert len(forks) <= 1, "more than one token is continued twice"
    return pred, np.int32(forks[0] if len(forks) else -2)


def kda_mixer(m, p, x, pred, fork, leave_out=()):
    """x [T, D]; pred int32 [T] and fork (``predecessors``). One token at a
    time."""
    h, d, taps = weights.linear_shape(m)
    t = x.shape[0]
    lb = float(m["linear_attn_config"]["gate_lower_bound"])
    pre = jnp.concatenate([_mm(x, p["wq"]), _mm(x, p["wk"]), _mm(x, p["wv"])], -1)  # [T, 3hd]
    w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], -1)  # [taps, 3hd]
    if "conv" in leave_out:
        w = w.at[:-1].set(0.0)
    f = _mm(_mm(x, p["f_a"]), p["f_b"]) + p["dt_bias"]
    g = lb * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f.reshape(t, h, d))
    beta = jax.nn.sigmoid(_mm(x, p["wb"]))  # [T, h]
    if "decay" in leave_out:
        g = jnp.zeros_like(g)
    if "beta" in leave_out:
        beta = jnp.ones_like(beta)
    zero = (jnp.zeros((h, d, d)), jnp.zeros((taps - 1, pre.shape[-1])))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    def step(carry, xs):
        (s_cur, tail_cur), saved = carry
        i, pre_t, g_t, b_t, pr = xs
        s, tail = jax.tree.map(
            lambda c, sv, z: jnp.where(pr == i - 1, c, jnp.where(pr == fork, sv, z)),
            (s_cur, tail_cur), saved, zero)
        s, tail = jax.tree.map(lambda a, z: jnp.where(pr < 0, z, a), (s, tail), zero)
        rows = jnp.concatenate([tail, pre_t[None]])  # [taps, 3hd]
        qkv = jax.nn.silu(jnp.sum(rows * w, axis=0))
        q, k, v = (a.reshape(h, d) for a in jnp.split(qkv, 3))
        q, k = unit(q) * d ** -0.5, unit(k)
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v - jnp.einsum("hd,hdv->hv", k, s, precision=HIGHEST))
        s = s + k[..., None] * u[:, None, :]
        o = jnp.einsum("hd,hdv->hv", q, s, precision=HIGHEST)
        cur = (s, rows[1:])
        saved = jax.tree.map(lambda c, sv: jnp.where(i == fork, c, sv), cur, saved)
        return (cur, saved), o

    _, o = jax.lax.scan(
        step, (zero, zero), (jnp.arange(t), pre, g, beta, pred))
    o = base.rms_norm(o, p["o_norm"], float(m["rms_norm_eps"]))  # [T, h, d]
    gate = jax.nn.sigmoid(_mm(_mm(x, p["wg_a"]), p["wg_b"]))
    return _mm(o.reshape(t, -1) * gate, p["wo"])


def latent_mixer(m, p, x, positions, mask, leave_out=()):
    h = int(m["num_attention_heads"])
    dn, dv = int(m["qk_nope_head_dim"]), int(m["v_head_dim"])
    eps = float(m["rms_norm_eps"])
    t = x.shape[0]
    q = _mm(base.rms_norm(_mm(x, p["q_a"]), p["q_a_norm"], eps), p["q_b"]).reshape(t, h, dn)
    kv = _mm(base.rms_norm(_mm(x, p["kv_a"]), p["kv_a_norm"], eps), p["kv_b"]).reshape(t, h, dn + dv)
    k, v = kv[..., :dn], kv[..., dn:]
    if "nope" in leave_out:
        cos, sin = base._rope_cos_sin(positions, dn, 10000.0)
        q = q * cos[:, None, :] + base._rotate_half(q) * sin[:, None, :]
        k = k * cos[:, None, :] + base._rotate_half(k) * sin[:, None, :]

    def one_head(args):
        qh, kh, vh = args
        s = jnp.einsum("qd,kd->qk", qh, kh, precision=HIGHEST) * dn ** -0.5
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", a, vh, precision=HIGHEST)

    o = jax.lax.map(one_head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return _mm(o.swapaxes(0, 1).reshape(t, h * dv), p["wo"])


def _router_m(m):
    return {**m, "n_routed_experts": weights.router_width(m)}


def moe(m, p, x, leave_out=()):
    """The held experts' part of the layer's result plus the shared expert."""
    held = weights.held_experts(m)
    combine = base.route(_router_m(m), p, x)[:, held.start:held.stop]  # [T, E held]

    def one(acc, ew):
        gate, up, down, c = ew
        return acc + c[:, None] * swiglu(m, x, gate, up, down, leave_out), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], combine.T))
    if "shared_gate" in p:
        out = out + swiglu(m, x, p["shared_gate"], p["shared_up"], p["shared_down"], leave_out)
    return out


def _sublayer(m, p, x, hc, norm, fn, quant, leave_out):
    """x [T, n, D] through one mHC-wrapped sublayer ``fn`` ([T, D] -> [T, D]).
    Without ``mhc``: every stream gets the plain residual ``X[i] + y`` of the
    streams' mean (what a model without hyper-connections would compute)."""
    eps = float(m["rms_norm_eps"])
    if "mhc" in leave_out:
        y = fn(base.rms_norm(x.mean(1), p[norm]["scale"], eps))
        return base._act(x + y[:, None, :], quant)
    h_pre, h_post, h_res = hc_mixes(m, p[hc], x, leave_out)
    u = jnp.einsum("ti,tid->td", h_pre, x, precision=HIGHEST)
    y = fn(base.rms_norm(u, p[norm]["scale"], eps))
    out = jnp.einsum("tij,tjd->tid", h_res, x, precision=HIGHEST) + h_post[..., None] * y[:, None, :]
    return base._act(out, quant)


@partial(jax.jit, static_argnums=(0, 7, 8, 9))
def _layer(mkey, p, x, positions, mask, pred, fork, linear, quant, leave_out):
    m = dict(mkey)
    m["linear_attn_config"] = dict(m["linear_attn_config"])
    if linear:
        mixer = lambda h: kda_mixer(m, p["attn"], h, pred, fork, leave_out)  # noqa: E731
    else:
        mixer = lambda h: latent_mixer(m, p["attn"], h, positions, mask, leave_out)  # noqa: E731
    x = _sublayer(m, p, x, "hc_attn", "input_layernorm", mixer, quant, leave_out)
    chosen = None
    if "router" in p["mlp"]:
        mlp = lambda h: moe(m, p["mlp"], h, leave_out)  # noqa: E731
        # what the router saw: the MLP's own normed input
        if "mhc" in leave_out:
            u = x.mean(1)
        else:
            u = jnp.einsum("ti,tid->td", hc_mixes(m, p["hc_mlp"], x, leave_out)[0], x,
                           precision=HIGHEST)
        hin = base.rms_norm(u, p["post_attention_layernorm"]["scale"], float(m["rms_norm_eps"]))
        chosen = base.choose(_router_m(m), p["mlp"], hin)[1]
    else:
        mlp = lambda h: swiglu(m, h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"],  # noqa: E731
                               leave_out)
    x = _sublayer(m, p, x, "hc_mlp", "post_attention_layernorm", mlp, quant, leave_out)
    return x, chosen


@partial(jax.jit, static_argnums=(0,))
def _head(mkey, norm, head, x_rows):
    m = dict(mkey)
    return _mm(base.rms_norm(x_rows.sum(1), norm["scale"], float(m["rms_norm_eps"])), head["kernel"])


def _mkey(model: dict):
    keep = ("num_attention_heads", "qk_nope_head_dim", "v_head_dim", "kv_lora_rank",
            "q_lora_rank", "rms_norm_eps", "hc_mult", "hc_eps", "hc_sinkhorn_iters",
            "swiglu_limit", "n_routed_experts", "ep_size", "ep_rank", "num_experts_per_tok",
            "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor")
    la = model["linear_attn_config"]
    la_key = tuple((k, la[k]) for k in ("num_heads", "head_dim", "short_conv_kernel_size",
                                        "gate_lower_bound"))
    return tuple((k, model[k]) for k in keep if model.get(k) is not None) + (
        ("linear_attn_config", la_key),)


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
    if set(leave_out) - set(PARTS):
        raise ValueError(f"unknown parts {sorted(set(leave_out) - set(PARTS))}: {PARTS}")
    longest = max(int(np.max(s["positions"])) + 1 for s in seqs)
    assert longest <= int(model["index_topk"]), (
        f"{longest} tokens: past index_topk the indexer selects keys, which is not in this reference")
    mkey, leave_out = _mkey(model), tuple(sorted(leave_out))
    names = weights.layer_names(model)
    n = int(model["hc_mult"])
    emb = layer_weights(model, seed, names[0], quant)["embedding"]
    xs = [jnp.repeat(emb[jnp.asarray(s["ids"])][:, None, :], n, axis=1) for s in seqs]
    del emb
    pos = [jnp.asarray(s["positions"], jnp.int32) for s in seqs]
    masks = [jnp.asarray(s["mask"]) for s in seqs]
    preds = [predecessors(np.asarray(s["mask"])) for s in seqs]
    for i, name in enumerate(names[1:-2]):
        p = layer_weights(model, seed, name, quant)
        linear = weights.is_linear_layer(model, i)
        outs = [_layer(mkey, p, x, pp, mk, pr, fork, linear, quant, leave_out)
                for x, pp, mk, (pr, fork) in zip(xs, pos, masks, preds)]
        xs = [o[0] for o in outs]
        if taps is not None and outs[0][1] is not None:
            taps.append([np.sort(np.asarray(o[1])[s["rows"]], -1) for o, s in zip(outs, seqs)])
        del p, outs
    norm = layer_weights(model, seed, names[-2], quant)
    head = layer_weights(model, seed, names[-1], quant)
    return [np.asarray(_head(mkey, norm, head, x[jnp.asarray(s["rows"], jnp.int32)]))
            for x, s in zip(xs, seqs)]
