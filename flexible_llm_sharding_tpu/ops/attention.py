"""Masked multi-head attention with grouped-query (GQA) support.

This is the FLOP core the reference delegated to transformers' CUDA kernels
(``/root/reference/utils.py:272-279``). TPU-first design choices:

- QK^T and PV matmuls stay in the model dtype (bf16/fp16) so they tile onto
  the MXU; only the softmax is done in float32 (matching HF's eager path).
- The mask is a boolean computed from ``iota`` inside the jitted function —
  the reference materialises a dense 4096x4096 fp16 mask (32 MB resident,
  ``/root/reference/utils.py:219-220``); here the mask is fused by XLA and
  never lives in HBM.
- No data-dependent shapes: prefix lengths are dynamic *values* folded into
  the mask, shapes are static per bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_PRECISION = jax.lax.Precision.HIGHEST  # no-op for bf16/fp16 MXU operands


def _grouped_q(q: jax.Array, n_kv: int) -> jax.Array:
    """[..., Lq, n_q, hd] -> [..., Lq, n_kv, g, hd] without copying."""
    *lead, lq, n_q, hd = q.shape
    return q.reshape(*lead, lq, n_kv, n_q // n_kv, hd)


def _softcap(scores: jax.Array, cap: float | None) -> jax.Array:
    """Gemma2 attention-logit softcapping: cap * tanh(scores / cap), applied
    to the scaled fp32 scores BEFORE the mask (HF eager_attention_forward
    order: scale -> softcap -> mask -> softmax)."""
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def _softmax_with_sink(scores: jax.Array, sink, n_kv: int) -> jax.Array:
    """Softmax over the last axis of ``scores`` [..., n_kv, g, Lq, Lk] (fp32).
    ``sink`` [n_q] (or None) is a learned per-head logit that joins the
    denominator and carries no value: one more column in the softmax, dropped
    again afterwards (MiMo-V2's ``attention_sink_bias``; HF concatenates it
    the same way)."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(n_kv, -1, 1, 1), (*scores.shape[:-1], 1)
    )
    return jax.nn.softmax(jnp.concatenate([scores, col], axis=-1), axis=-1)[..., :-1]


def _local_clause(
    mask: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    window: int | None,
    sliding,
    chunk: int | None = None,
):
    """AND the local-attention visibility into ``mask``.

    Two local forms (mutually exclusive): a sliding ``window`` (visible iff
    q_pos - k_pos < window, HF convention) or llama4 ``chunk``ed attention
    (visible iff q_pos // chunk == k_pos // chunk). ``sliding`` is None
    (applies statically) or a traced bool scalar (per-layer toggle under a
    scan): masked iff sliding AND outside the local region.
    """
    if window is None and chunk is None:
        return mask
    if window is not None:
        in_local = (q_pos - k_pos) < window
    else:
        in_local = (q_pos // chunk) == (k_pos // chunk)
    if sliding is not None:
        in_local = jnp.logical_or(jnp.logical_not(sliding), in_local)
    return mask & in_local


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
    scale: float | None = None,
    softcap: float | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Scaled dot-product attention with GQA via grouped einsums.

    q: [..., Lq, n_q, hd]; k, v: [..., Lk, n_kv, hd] with n_q % n_kv == 0.
    mask: broadcastable to [..., Lq, Lk]; True = attend, False = masked.
    sink: optional per-head logit [n_q] in the softmax's denominator.
    Returns [..., Lq, n_q, hd].

    KV heads are never replicated in memory (no jnp.repeat): queries are
    reshaped to [n_kv, group] and contracted against the n_kv heads directly —
    the GQA equivalent of torch's .expand view in the reference's KV trick.
    """
    n_q, n_kv = q.shape[-2], k.shape[-2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    qr = _grouped_q(q, n_kv)
    # [..., n_kv, g, Lq, Lk] in model dtype (MXU), softmax in fp32.
    scores = jnp.einsum("...qngh,...knh->...ngqk", qr, k, precision=_PRECISION)
    scores = _softcap(scores.astype(jnp.float32) * scale, softcap)
    if mask is not None:
        scores = jnp.where(mask[..., None, None, :, :], scores, _NEG_INF)
    probs = _softmax_with_sink(scores, sink, n_kv).astype(q.dtype)
    out = jnp.einsum("...ngqk,...knh->...qngh", probs, v, precision=_PRECISION)
    # V's own head dim (MLA: v_head_dim != qk head dim).
    return out.reshape(*q.shape[:-1], v.shape[-1])


def prefix_shared_attention(
    q: jax.Array,
    k_prefix: jax.Array,
    v_prefix: jax.Array,
    k_suffix: jax.Array,
    v_suffix: jax.Array,
    prefix_len: jax.Array,
    scale: float | None = None,
    window: int | None = None,
    softcap: float | None = None,
    sliding=None,
    chunk: int | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Attention of S suffix continuations over [shared prefix KV ; own causal KV].

    The reference expands the prefix KV across suffixes with torch ``.expand``
    (a view, ``/root/reference/utils.py:277``); the naive JAX translation
    (broadcast_to + concatenate) would materialise S copies in HBM. Here the
    prefix KV stays [Lp, n_kv, hd] — shared by every suffix and every query
    group — and the two score blocks are computed by separate einsums with a
    joint softmax across their concatenation.

    q: [S, Ls, n_q, hd] (RoPE already applied at positions prefix_len+i);
    k_prefix/v_prefix: [Lp, n_kv, hd]; k_suffix/v_suffix: [S, Ls, n_kv, hd];
    prefix_len: int32 scalar — prefix keys at j >= prefix_len are padding;
    sink: optional per-head logit [n_q] in the softmax's denominator.
    Returns [S, Ls, n_q, hd].
    """
    s, ls, n_q, hd = q.shape
    lp, n_kv, _ = k_prefix.shape
    if scale is None:
        scale = 1.0 / (hd**0.5)

    qr = _grouped_q(q, n_kv)  # [S, Ls, n_kv, g, hd]
    scores_p = jnp.einsum("sqngh,knh->sngqk", qr, k_prefix, precision=_PRECISION)
    scores_s = jnp.einsum("sqngh,sknh->sngqk", qr, k_suffix, precision=_PRECISION)
    scores = _softcap(
        jnp.concatenate([scores_p, scores_s], axis=-1).astype(jnp.float32) * scale,
        softcap,
    )  # [S, n_kv, g, Ls, Lp+Ls]

    # Prefix keys visible iff real; suffix keys causal. With a sliding
    # window, absolute positions are: query qi at prefix_len + qi, prefix key
    # kj at kj, suffix key kj at prefix_len + (kj - lp) — mask whenever the
    # query-key distance reaches the window (HF convention: dist < window).
    kj = jnp.arange(lp + ls)[None, :]
    qi = jnp.arange(ls)[:, None]
    mask = jnp.where(kj < lp, kj < prefix_len, (kj - lp) <= qi)  # [Ls, Lp+Ls]
    if window is not None or chunk is not None:
        abs_k = jnp.where(kj < lp, kj, prefix_len + kj - lp)
        mask = _local_clause(mask, prefix_len + qi, abs_k, window, sliding, chunk)
    scores = jnp.where(mask[None, None, None], scores, _NEG_INF)

    probs = _softmax_with_sink(scores, sink, n_kv).astype(q.dtype)
    probs_p, probs_s = probs[..., :lp], probs[..., lp:]
    out = jnp.einsum("sngqk,knh->sqngh", probs_p, v_prefix, precision=_PRECISION)
    out = out + jnp.einsum(
        "sngqk,sknh->sqngh", probs_s, v_suffix, precision=_PRECISION
    )
    return out.reshape(s, ls, n_q, v_prefix.shape[-1])


def decode_attention(
    q: jax.Array,
    k_prefix: jax.Array,
    v_prefix: jax.Array,
    k_suffix: jax.Array,
    v_suffix: jax.Array,
    k_gen: jax.Array,
    v_gen: jax.Array,
    prefix_len: jax.Array,
    suffix_eos: jax.Array,
    t: jax.Array,
    scale: float | None = None,
    window: int | None = None,
    softcap: float | None = None,
    sliding=None,
    chunk: int | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over three cached KV regions, one joint softmax.

    The KV-cache decode mode's hot op (not in the reference — its generation
    loop re-runs the whole prompt per token, ``/root/reference/main.py:65-76``;
    SURVEY.md §3.5 calls this the known scaling cliff). The queries are the
    K NEWEST tokens per suffix (K=1 for plain decode; K=draft+1 for the
    speculative verify step), occupying generated-KV slots ``t .. t+K-1``.
    Query j attends jointly (one softmax) over:

    - the shared prefix KV  (keys i < prefix_len),
    - its own suffix KV     (keys i <= suffix_eos[s]),
    - generated tokens' KV up to ITSELF (keys i <= t[s] + j — causal among
      the K fed tokens, whose KV is already written at those slots).

    q [S, K, n_q, hd]; k/v_prefix [Lp, n_kv, hd]; k/v_suffix [S, Ls, n_kv, hd];
    k/v_gen [S, T, n_kv, hd] (slots t..t+K-1 already hold this step's KV);
    prefix_len int32 scalar; t: int32 scalar or per-suffix [S] (speculative
    passes advance each suffix by its own accepted count); suffix_eos int32
    [S]; sink: optional per-head logit [n_q] in the softmax's denominator.
    Returns [S, K, n_q, hd].
    """
    s, kq, n_q, hd = q.shape
    n_kv = k_prefix.shape[-2]
    if scale is None:
        scale = 1.0 / (hd**0.5)
    lp = k_prefix.shape[0]
    ls = k_suffix.shape[1]
    tmax = k_gen.shape[1]
    base = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (s,))  # [S]
    jq = jnp.arange(kq)

    qr = _grouped_q(q, n_kv)  # [S, K, n_kv, g, hd]
    sp = jnp.einsum("sqngh,knh->sngqk", qr, k_prefix, precision=_PRECISION)
    ss = jnp.einsum("sqngh,sknh->sngqk", qr, k_suffix, precision=_PRECISION)
    sg = jnp.einsum("sqngh,sknh->sngqk", qr, k_gen, precision=_PRECISION)
    scores = _softcap(
        jnp.concatenate([sp, ss, sg], axis=-1).astype(jnp.float32) * scale, softcap
    )  # [S, n_kv, g, K, Lp+Ls+T]

    jp = jnp.arange(lp)[None, None, :] < prefix_len  # [1, 1, Lp]
    js = jnp.arange(ls)[None, None, :] <= suffix_eos[:, None, None]  # [S,1,Ls]
    jg = (
        jnp.arange(tmax)[None, None, :]
        <= base[:, None, None] + jq[None, :, None]
    )  # [S, K, T]
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(jp, (s, kq, lp)),
            jnp.broadcast_to(js, (s, kq, ls)),
            jg,
        ],
        axis=-1,
    )  # [S, K, Lp+Ls+T]
    if window is not None or chunk is not None:
        # Absolute positions: query j at prefix_len + suffix_eos[s] + 1 +
        # t[s] + j; prefix key i at i, suffix key i at prefix_len + i,
        # generated key i at prefix_len + suffix_eos[s] + 1 + i. Sliding
        # window masks keys at distance >= window (HF convention).
        q_pos = (
            prefix_len + suffix_eos[:, None] + 1 + base[:, None] + jq[None, :]
        )  # [S, K]
        abs_k = jnp.concatenate(
            [
                jnp.broadcast_to(jnp.arange(lp)[None, :], (s, lp)),
                prefix_len + jnp.broadcast_to(jnp.arange(ls)[None, :], (s, ls)),
                prefix_len
                + suffix_eos[:, None]
                + 1
                + jnp.broadcast_to(jnp.arange(tmax)[None, :], (s, tmax)),
            ],
            axis=-1,
        )  # [S, Lp+Ls+T]
        mask = _local_clause(
            mask, q_pos[..., None], abs_k[:, None, :], window, sliding, chunk
        )
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

    probs = _softmax_with_sink(scores, sink, n_kv).astype(q.dtype)
    pp, ps, pg = (
        probs[..., :lp],
        probs[..., lp : lp + ls],
        probs[..., lp + ls :],
    )
    out = jnp.einsum("sngqk,knh->sqngh", pp, v_prefix, precision=_PRECISION)
    out = out + jnp.einsum("sngqk,sknh->sqngh", ps, v_suffix, precision=_PRECISION)
    out = out + jnp.einsum("sngqk,sknh->sqngh", pg, v_gen, precision=_PRECISION)
    return out.reshape(s, kq, n_q, v_prefix.shape[-1])


def causal_mask(
    lq: int,
    lk: int,
    offset: int = 0,
    window: int | None = None,
    chunk: int | None = None,
) -> jax.Array:
    """Boolean causal mask [lq, lk]: query i attends key j iff j <= i + offset,
    and — with a sliding ``window`` (Mistral-style) — iff additionally
    ``(i + offset) - j < window`` (HF masking_utils convention) — or with a
    llama4 ``chunk`` — iff additionally both positions share a chunk."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
    mask = kj <= qi + offset
    if window is not None:
        mask &= (qi + offset) - kj < window
    if chunk is not None:
        mask &= ((qi + offset) // chunk) == (kj // chunk)
    return mask
