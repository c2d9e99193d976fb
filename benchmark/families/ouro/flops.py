"""Operations and bytes an ``ouro`` scoring batch NEEDS, from shapes alone:
what the tokens need, not what the program computes (its kernels work in
whole blocks over padded buckets).

A batch is ``prompts`` prefixes (BOS counted) and ``prompts * suffixes``
suffixes, the traffic file's quantile lengths. Every token visits every layer
``total_ut_steps`` times, and every visit is whole: the projections, the causal
attention over the keys of that step, the SwiGLU. A prefix token at position i
attends to i + 1 keys and a suffix token at offset j behind a prefix of P
tokens to P + j + 1 (linear in P, so the mean prefix stands for the batch's
pairing exactly). The final norm and the exit gate (2 x hidden FLOPs a scored
row and step) are left out: under a millionth of the rest.
"""

from __future__ import annotations

from benchmark import traffic as tr
from benchmark.families.ouro import weights

BF16 = 2  # bytes


def batch_lengths(traffic: dict) -> tuple[list[int], list[int]]:
    """(prefix lengths with BOS, suffix lengths) of one batch."""
    n, s = int(traffic["prompts"]), int(traffic["suffixes"])
    pre = [x + 1 for x in tr.quantile_lengths(traffic["prefix_tokens"], n)]
    return pre, tr.quantile_lengths(traffic["suffix_tokens"], n * s)


def layer_visits(model: dict) -> int:
    """Decoder-layer visits of one batch: the stack once a step."""
    return int(model["num_hidden_layers"]) * int(model["total_ut_steps"])


def attended_keys(pre: list[int], suf: list[int]) -> float:
    """Keys all tokens of a batch attend to in one layer visit."""
    mean_p = sum(pre) / len(pre)
    return (sum(p * (p + 1) / 2 for p in pre)
            + sum(mean_p * x + x * (x + 1) / 2 for x in suf))


def flash_need(model: dict, traffic: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) each flash-kernel call of one layer visit needs over one
    batch: one causal call per prompt for its prefix, one prefix-shared call
    per prompt for its suffixes. FLOPs: QK^T and PV over the keys a query can
    see, 2 a MAC. Bytes: q read, the keys and values read once (the suffix
    call reads its prompt's prefix keys and values once for all its suffixes),
    the output written, in bfloat16."""
    nq, nkv, hd = weights.attn_shape(model)
    pre, suf = batch_lengths(traffic)
    s = len(suf) // len(pre)
    # a prompt's s suffixes at the batch's mean: which suffixes a prompt gets
    # changes with the seed, their tokens and their own causal keys do not
    rows = s * sum(suf) / len(suf)
    own_keys = s * sum(x * (x + 1) / 2 for x in suf) / len(suf)
    per_key = 2.0 * nq * 2 * hd
    kv_row, qo_row = 2 * nkv * hd * BF16, 2 * nq * hd * BF16
    calls = []
    for p in pre:
        calls.append((per_key * p * (p + 1) / 2, p * (qo_row + kv_row)))
        calls.append((per_key * (rows * p + own_keys), rows * (qo_row + kv_row) + p * kv_row))
    return calls


def flash_roofline_s(model: dict, traffic: dict, peaks: dict) -> float:
    """The least time the chip could spend in the flash kernels over one
    batch: per call the larger of FLOPs over the bf16 peak and bytes over the
    HBM rate, summed over the calls of every layer visit."""
    return layer_visits(model) * sum(
        max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
        for f, b in flash_need(model, traffic)
    )


def needed_flops(model: dict, traffic: dict) -> float:
    """FLOPs one batch needs (2 per MAC): every layer visit's projections,
    SwiGLU and causal attention, and the head on the scored rows (one per
    suffix, once: it reads one step's output) over the whole vocabulary."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    nq, nkv, hd = weights.attn_shape(model)
    pre, suf = batch_lengths(traffic)
    tokens = sum(pre) + sum(suf)
    proj = d * (nq * hd + 2 * nkv * hd) + nq * hd * d
    per_visit = 2.0 * tokens * (proj + 3 * d * f) + 2.0 * attended_keys(pre, suf) * nq * 2 * hd
    return layer_visits(model) * per_visit + 2.0 * d * int(model["vocab_size"]) * len(suf)
