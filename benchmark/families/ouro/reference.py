"""Plain float32 reference for ``ouro`` (Ouro, a looped language model): ONE
stack of L layers that every token visits T = ``total_ut_steps`` times.

With E the embedding, N an RMSNorm with a plain learned scale (``x *
rsqrt(mean(x^2) + eps) * w``), and the SAME L layers' weights at every step:

    h_0 = E[ids]
    for t = 1..T:
        x = h_{t-1}
        for l = 1..L:
            a = x + N2_l(Attn_l(N1_l(x)))    # causal softmax at 1/sqrt(head_dim),
                                             # rotary over the whole head (half
                                             # rotation) at rope_theta; keys and
                                             # values of step t only
            x = a + N4_l(MLP_l(N3_l(a)))     # W_down(silu(W_gate u) * (W_up u))
        h_t = N_f(x)                         # the final norm closes EVERY step;
                                             # its output feeds step t + 1
        lambda_t = sigmoid(w_g . h_t + b_g)  # the exit gate, one scalar a token
    logits = W_head h_{t*}

with t* = T at ``early_exit_threshold`` q >= 1 (the published 1). At q < 1:
p_t = lambda_t * prod_{j<t}(1 - lambda_j) for t < T and p_T = prod_{j<T}(1 -
lambda_j); a token reads the first t whose cumulative sum of p reaches q.

What the catalog's ``config`` does not carry (the four norms and their order,
the final norm inside the loop, the gate's formula and bias, the rule at q < 1,
no biases, no q/k norm, the tensor names) is listed with its reason under
``assumed`` in ``benchmark/configs/ouro-2.6b.json``; it is the family's
published modelling code as the session that wrote this file knew it.
Departures from that description: none in the mathematics. In the order of
computation: layer by layer over all sequences, and a layer's weights are
REGENERATED from the seed at each of its T visits (four generations of each
layer; 48 float32 layers held would be 9.9 GB beside what the program leaves
on the chip, one is 0.2 GB); one head at a time, so that a [T, T] block is
all that sits beside it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no batching. It imports nothing of the program and gets its
weights from ``weights.layer_tensors`` (the seed alone). What is the same for
every family (the sequence layouts with their masks, the weights'
lower-precision controls, RMSNorm, SwiGLU, rotary tables) is imported from
``benchmark/reference.py``.

``leave_out`` names parts of the mathematics to drop or change, for the
controls that show the comparison sees each: ``last_step`` (T - 1 steps),
``loop_norm`` (no final norm between steps: it closes the last step only),
``output_norms`` (no N2 and no N4), ``layer_order`` (the layers reversed
inside every step).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as base
from benchmark.families.ouro import weights

HIGHEST = base.HIGHEST
scoring_sequence = base.scoring_sequence
causal_sequence = base.causal_sequence
PARTS = ("last_step", "loop_norm", "output_norms", "layer_order")


def attention(m, p, x, positions, mask):
    nq, nkv, hd = int(m["num_attention_heads"]), int(m["num_key_value_heads"]), int(m["head_dim"])
    t = x.shape[0]
    cos, sin = base._rope_cos_sin(positions, hd, float(m["rope_theta"]))
    rope = lambda a: a * cos[:, None, :] + base._rotate_half(a) * sin[:, None, :]  # noqa: E731
    q = rope(base._mm(x, p["wq"]).reshape(t, nq, hd))
    k = rope(base._mm(x, p["wk"]).reshape(t, nkv, hd))
    v = base._mm(x, p["wv"]).reshape(t, nkv, hd)
    g = nq // nkv  # 1 in the published models: plain multi-head attention

    def one_kv_head(args):
        qg, kh, vh = args  # [T, g, hd], [T, hd], [T, hd]
        sc = jnp.einsum("qgd,kd->gqk", qg, kh, precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", a, vh, precision=HIGHEST)

    o = jax.lax.map(
        one_kv_head, (q.reshape(t, nkv, g, hd).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))
    )  # [nkv, T, g, hd]
    return base._mm(o.swapaxes(0, 1).reshape(t, nq * hd), p["wo"])


@partial(jax.jit, static_argnums=(0, 5, 6))
def _layer(mkey, p, x, positions, mask, quant, leave_out):
    m = dict(mkey)
    eps = float(m["rms_norm_eps"])
    out_norm = (lambda y, name: y) if "output_norms" in leave_out else (
        lambda y, name: base.rms_norm(y, p[name]["scale"], eps))
    y = attention(m, p["attn"], base.rms_norm(x, p["input_layernorm"]["scale"], eps),
                  positions, mask)
    a = base._act(x + out_norm(y, "post_attention_layernorm"), quant)
    u = base.rms_norm(a, p["pre_feedforward_layernorm"]["scale"], eps)
    y = base._swiglu(u, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return base._act(a + out_norm(y, "post_feedforward_layernorm"), quant)


@partial(jax.jit, static_argnums=(0,))
def _step_end(mkey, norm, x):
    """h_t = N_f(x) over every row, and the gate's lambda_t [T] beside it."""
    h = base.rms_norm(x, norm["scale"], float(dict(mkey)["rms_norm_eps"]))
    z = base._mm(h, norm["gate"]["kernel"])[:, 0] + norm["gate"]["bias"][0]
    return h, jax.nn.sigmoid(z)


def exit_steps(lambdas: np.ndarray, q: float) -> np.ndarray:
    """lambdas float [T, rows] -> the 1-based step each row reads: T at q >= 1,
    else the first t whose cumulative exit probability reaches q."""
    t = lambdas.shape[0]
    if q >= 1:
        return np.full(lambdas.shape[1], t)
    keep = np.cumprod(1.0 - lambdas[:-1], axis=0)  # prod_{j<=t}(1 - lambda_j), t < T
    remaining = np.concatenate([np.ones((1, lambdas.shape[1])), keep])  # before step t
    p = np.concatenate([lambdas[:-1] * remaining[:-1], remaining[-1:]])
    return 1 + np.argmax(np.cumsum(p, axis=0) >= q, axis=0)


def _mkey(model: dict):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "rms_norm_eps")
    m = dict(model)
    m.setdefault("head_dim", int(m["hidden_size"]) // int(m["num_attention_heads"]))
    return tuple((k, m[k]) for k in keep)


def layer_weights(model: dict, seed: int, name: str, quant=None) -> dict:
    flat = weights.layer_tensors(model, seed, name)
    return weights.unflatten({k: base._prep(a, quant) for k, a in flat.items()})


def forward_rows(model: dict, seed: int, seqs: list[dict], quant=None, taps: list | None = None,
                 leave_out=()) -> list[np.ndarray]:
    """As ``benchmark.reference.forward_rows``: ``seqs`` are dicts with
    ``ids`` [T], ``positions`` [T], ``mask`` [T, T] and ``rows``; one float32
    [len(rows), vocab] logits array per sequence comes back. ``taps`` is the
    drivers' interface; where given it gets one dict, the wanted rows' gate
    probabilities ``lambdas`` [steps, rows] and chosen ``steps`` [rows] per
    sequence. ``leave_out``: see the module's docstring."""
    leave_out = tuple(sorted(leave_out))
    unknown = set(leave_out) - set(PARTS)
    if unknown:
        raise ValueError(f"unknown parts {sorted(unknown)} (one of {PARTS})")
    mkey = _mkey(model)
    steps = int(model["total_ut_steps"]) - ("last_step" in leave_out)
    q = float(model.get("early_exit_threshold", 1.0))
    names = weights.layer_names(model)
    emb = layer_weights(model, seed, names[0], quant)["embedding"]
    xs = [emb[jnp.asarray(s["ids"])] for s in seqs]
    del emb
    pos = [jnp.asarray(s["positions"], jnp.int32) for s in seqs]
    masks = [jnp.asarray(s["mask"]) for s in seqs]
    rows = [jnp.asarray(s["rows"], jnp.int32) for s in seqs]
    norm = layer_weights(model, seed, names[-2], quant)
    stack = names[1:-2][::-1] if "layer_order" in leave_out else names[1:-2]
    h_rows, lambdas = [], []  # per step: per sequence [rows, D], [rows]
    for t in range(steps):
        for name in stack:
            p = layer_weights(model, seed, name, quant)  # regenerated at every visit
            xs = [_layer(mkey, p, x, pp, mk, quant, leave_out)
                  for x, pp, mk in zip(xs, pos, masks)]
            del p
        ends = [_step_end(mkey, norm, x) for x in xs]
        h_rows.append([h[r] for (h, _), r in zip(ends, rows)])
        lambdas.append([lam[r] for (_, lam), r in zip(ends, rows)])
        if "loop_norm" not in leave_out:
            xs = [h for h, _ in ends]
    head = layer_weights(model, seed, names[-1], quant)
    out = []
    for i in range(len(seqs)):
        lam = np.stack([np.asarray(lambdas[t][i], np.float64) for t in range(steps)])
        chosen = exit_steps(lam, q)
        h = jnp.stack([h_rows[t][i] for t in range(steps)])  # [steps, rows, D]
        picked = h[jnp.asarray(chosen - 1), jnp.arange(h.shape[1])]
        out.append(np.asarray(base._mm(picked, head["kernel"])))
        if taps is not None:
            taps.append({"lambdas": lam, "steps": chosen})
    return out
