"""Operations and bytes a ``minicpm_sala`` scoring batch NEEDS, from shapes
alone: what the tokens need, not what the program computes (its kernels work
in whole blocks and chunks, over padded buckets).

A batch is ``prompts`` prefixes (BOS counted) and ``prompts * suffixes``
suffixes, the traffic file's quantile lengths. In a ``minicpm4`` layer a
prefix token at position i attends to i + 1 keys and a suffix token at offset
j behind a prefix of P tokens to P + j + 1 (linear in P, so the mean prefix
stands for the batch's pairing exactly). In a ``lightning-attn`` layer every
token costs the recurrence the same, wherever it stands: per head
``2 d dv`` FLOPs to add ``k_t^T v_t`` to the state and ``2 d dv`` to read
``q_t S_t`` out, whatever chunk size an implementation walks it in.
"""

from __future__ import annotations

from benchmark import traffic as tr
from benchmark.families.minicpm_sala import weights

BF16, F32 = 2, 4  # bytes


def batch_lengths(traffic: dict) -> tuple[list[int], list[int]]:
    """(prefix lengths with BOS, suffix lengths) of one batch."""
    n, s = int(traffic["prompts"]), int(traffic["suffixes"])
    pre = [x + 1 for x in tr.quantile_lengths(traffic["prefix_tokens"], n)]
    return pre, tr.quantile_lengths(traffic["suffix_tokens"], n * s)


def n_layers(model: dict, linear: bool) -> int:
    return sum(weights.is_linear_layer(model, i) == linear
               for i in range(int(model["num_hidden_layers"])))


def attended_keys(pre: list[int], suf: list[int]) -> float:
    """Keys all tokens of a batch attend to in one softmax layer."""
    mean_p = sum(pre) / len(pre)
    return (sum(p * (p + 1) / 2 for p in pre)
            + sum(mean_p * x + x * (x + 1) / 2 for x in suf))


def recurrence_flops_per_token(model: dict) -> float:
    """One ``lightning-attn`` layer's recurrence, a token: per head the state
    update and the read-out, 2 FLOPs a MAC."""
    h, _, d = weights.attn_shape(model, True)
    return h * 4.0 * d * d


def lightning_need(model: dict, traffic: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) each call of the lightning kernel needs in one layer over
    one batch: one call per prompt for its prefix (from a zero state), one per
    prompt for its suffixes (from the prefix's state). Bytes: q, k, v read
    and o written once, in bfloat16; a state is float32 [heads, d, d]: the
    prefix call writes one, the suffix call reads one."""
    h, _, d = weights.attn_shape(model, True)
    pre, suf = batch_lengths(traffic)
    s = len(suf) // len(pre)
    rows_suf = s * sum(suf) / len(suf)  # a prompt's suffix tokens, at the mean
    per_token_bytes = 4 * h * d * BF16
    state = h * d * d * F32
    per_token_flops = recurrence_flops_per_token(model)
    calls = []
    for p in pre:
        calls.append((per_token_flops * p, per_token_bytes * p + state))
        calls.append((per_token_flops * rows_suf, per_token_bytes * rows_suf + state))
    return calls


def lightning_roofline_s(model: dict, traffic: dict, peaks: dict) -> float:
    """The least time the chip could spend in the lightning kernel over one
    batch: per call the larger of FLOPs over the bf16 peak and bytes over the
    HBM rate, summed over the calls of every ``lightning-attn`` layer."""
    return n_layers(model, True) * sum(
        max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
        for f, b in lightning_need(model, traffic)
    )


def needed_flops(model: dict, traffic: dict) -> float:
    """FLOPs one batch needs (2 per MAC): each kind's projections and gate,
    the SwiGLU of every layer, the softmax layers' causal attention, the
    linear layers' recurrence, and the head on the scored rows (one per
    suffix) over the whole vocabulary."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    pre, suf = batch_lengths(traffic)
    tokens = sum(pre) + sum(suf)
    total = 0.0
    for linear in (True, False):
        nq, nkv, hd = weights.attn_shape(model, linear)
        gate = nq * hd if model.get("use_output_gate" if linear else "attn_use_output_gate") else 0
        proj = d * (nq * hd + 2 * nkv * hd + gate) + nq * hd * d
        per_layer = 2.0 * tokens * (proj + 3 * d * f)
        if linear:
            per_layer += tokens * recurrence_flops_per_token(model)
        else:
            per_layer += 2.0 * attended_keys(pre, suf) * nq * 2 * hd
        total += n_layers(model, linear) * per_layer
    return total + 2.0 * d * int(model["vocab_size"]) * len(suf)
