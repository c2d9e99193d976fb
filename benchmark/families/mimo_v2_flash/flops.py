"""Operations and bytes a ``mimo_v2_flash`` scoring batch NEEDS, from shapes
alone: what the tokens need, not what the program computes (it computes every
held expert for every row today, and its kernels work in whole blocks).

A batch is ``prompts`` prefixes (BOS counted) and ``prompts * suffixes``
suffixes, the traffic file's quantile lengths. A prefix token at position i
attends to i + 1 keys; a suffix token at offset j of a suffix behind a prefix
of P tokens to P + j + 1: in a window layer both are clipped to the window.
Which suffixes ride which prefix changes from batch to batch; a full layer's
cost is linear in P, so the mean prefix stands for the pairing exactly, and a
window layer's does not depend on it (every prefix is longer than the window).
"""

from __future__ import annotations

from benchmark import traffic as tr
from benchmark.families.mimo_v2_flash import weights

BF16 = 2  # bytes


def batch_lengths(traffic: dict) -> tuple[list[int], list[int]]:
    """(prefix lengths with BOS, suffix lengths) of one batch."""
    n, s = int(traffic["prompts"]), int(traffic["suffixes"])
    pre = [x + 1 for x in tr.quantile_lengths(traffic["prefix_tokens"], n)]
    return pre, tr.quantile_lengths(traffic["suffix_tokens"], n * s)


def _tri(n: int, cap: int | None) -> float:
    """sum over i < n of min(i + 1, cap): keys a causal run of n tokens
    attends to in all, under a window of ``cap``."""
    if cap is None or n <= cap:
        return n * (n + 1) / 2
    return cap * (cap + 1) / 2 + (n - cap) * cap


def attended_keys(pre: list[int], suf: list[int], window: int | None) -> tuple[float, float]:
    """(keys attended by all prefix tokens, by all suffix tokens) of a batch
    in one layer; ``window`` None for a full layer."""
    mean_p = sum(pre) / len(pre)
    by_prefix = sum(_tri(p, window) for p in pre)
    if window is None:
        by_suffix = sum(mean_p * x + x * (x + 1) / 2 for x in suf)
    else:  # P + j + 1 > window for every suffix token: all see ``window`` keys
        by_suffix = sum(min(mean_p + j + 1, window) for x in suf for j in range(x))
    return by_prefix, by_suffix


def attention_need(model: dict, traffic: dict, window: bool) -> list[tuple[float, float]]:
    """(FLOPs, bytes) each flash-kernel call of one layer of this kind needs
    over one batch: one causal call per prompt, one prefix-shared call per
    prompt. Bytes: q read, the keys and values a query can see read once
    (a full layer's whole prefix, shared by the suffixes; a window layer's
    last window - 1 prefix keys), the output written."""
    nq, nkv, hd, vd = weights.attn_shape(model, window)
    w = int(model["sliding_window"]) if window else None
    pre, suf = batch_lengths(traffic)
    s = len(suf) // len(pre)
    mean_suf = sum(suf) / len(suf)
    per_key = 2.0 * nq * (hd + vd)  # QK^T and PV, 2 FLOPs a MAC
    kv_row, q_row, o_row = nkv * (hd + vd) * BF16, nq * hd * BF16, nq * vd * BF16
    calls = []
    for p in pre:
        calls.append((per_key * _tri(p, w), p * (q_row + kv_row + o_row)))
        rows = s * mean_suf  # this prompt's suffix tokens, at the mean length
        visible_prefix = p if w is None else min(p, w - 1)
        keys = rows * w if w is not None else rows * p + rows * (mean_suf + 1) / 2
        calls.append((per_key * keys, rows * (q_row + kv_row + o_row) + visible_prefix * kv_row))
    return calls


def attention_roofline_s(model: dict, traffic: dict, peaks: dict) -> float:
    """The least time the chip could spend in the attention kernels of one
    batch: per call the larger of FLOPs over the bf16 peak and bytes over the
    HBM rate, summed over the calls of every layer."""
    n = int(model["num_hidden_layers"])
    total = 0.0
    for window in (False, True):
        layers = sum(weights.is_window_layer(model, i) == window for i in range(n))
        total += layers * sum(
            max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
            for f, b in attention_need(model, traffic, window)
        )
    return total


def needed_flops(model: dict, traffic: dict, held_assignments: float) -> float:
    """FLOPs one batch needs (2 per MAC): projections by layer kind, the
    dense first layer, the router at its whole width, ``held_assignments``
    routed token-expert pairs through a held expert's SwiGLU (the account's
    count, real tokens only), window-clipped attention, and the head on the
    scored rows (one per suffix) over the vocabulary held."""
    d, n = int(model["hidden_size"]), int(model["num_hidden_layers"])
    pre, suf = batch_lengths(traffic)
    tokens = sum(pre) + sum(suf)
    total = 0.0
    for i in range(n):
        window = weights.is_window_layer(model, i)
        nq, nkv, hd, vd = weights.attn_shape(model, window)
        proj = d * (nq * hd + nkv * hd + nkv * vd) + nq * vd * d
        if weights.is_moe_layer(model, i):
            mlp = d * weights.router_width(model)
        else:
            mlp = 3 * d * int(model["intermediate_size"])
        keys = sum(attended_keys(pre, suf, int(model["sliding_window"]) if window else None))
        total += 2.0 * (tokens * (proj + mlp) + keys * nq * (hd + vd))
    total += 2.0 * held_assignments * 3 * d * int(model["moe_intermediate_size"])
    return total + 2.0 * d * int(model["vocab_size"]) * len(suf)
