"""Operations and bytes a ``glm5_next_text`` scoring batch NEEDS, from shapes
alone: what the tokens need, not what the program computes (its kernels work
in whole chunks over padded buckets, and solve a chunk's triangular system
where the recurrence has none).

A batch is ``prompts`` prefixes (BOS counted) and ``prompts * suffixes``
suffixes, the traffic file's quantile lengths. In a latent layer a prefix
token at position i attends to i + 1 keys and a suffix token at offset j
behind a prefix of P tokens to P + j + 1 (linear in P, so the mean prefix
stands for the batch's pairing exactly). In a KDA layer every token costs the
recurrence the same, wherever it stands: per head ``d dv`` FLOPs to decay the
state, ``2 d dv`` to read ``k_t`` against it, ``2 d dv`` to write ``k_t
u_t^T`` and ``2 d dv`` to read ``q_t`` out: ``7 d dv``, whatever chunking
walks it.
"""

from __future__ import annotations

from benchmark import traffic as tr
from benchmark.families.glm5_next_text import weights

BF16, F32 = 2, 4  # bytes


def batch_lengths(traffic: dict) -> tuple[list[int], list[int]]:
    """(prefix lengths with BOS, suffix lengths) of one batch."""
    n, s = int(traffic["prompts"]), int(traffic["suffixes"])
    pre = [x + 1 for x in tr.quantile_lengths(traffic["prefix_tokens"], n)]
    return pre, tr.quantile_lengths(traffic["suffix_tokens"], n * s)


def n_layers(model: dict, kind) -> int:
    """Layers for which ``kind(model, i)`` holds."""
    return sum(bool(kind(model, i)) for i in range(int(model["num_hidden_layers"])))


def attended_keys(pre: list[int], suf: list[int]) -> float:
    """Keys all tokens of a batch attend to in one latent layer."""
    mean_p = sum(pre) / len(pre)
    return (sum(p * (p + 1) / 2 for p in pre)
            + sum(mean_p * x + x * (x + 1) / 2 for x in suf))


def recurrence_flops_per_token(model: dict) -> float:
    """One KDA layer's recurrence, a token: 7 d dv a head (see above)."""
    h, d, _ = weights.linear_shape(model)
    return h * 7.0 * d * d


def kda_projection_macs(model: dict) -> float:
    """MACs a token of one KDA layer's projections: q, k, v and o, the
    decay's and the gate's two-matrix waists, beta, and the three short
    convolutions."""
    d = int(model["hidden_size"])
    h, hd, taps = weights.linear_shape(model)
    return 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h + 3 * taps * h * hd


def latent_projection_macs(model: dict) -> float:
    d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
    qr, kvr = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    dn, dv = int(model["qk_nope_head_dim"]), int(model["v_head_dim"])
    return d * qr + qr * h * dn + d * kvr + kvr * h * (dn + dv) + h * dv * d


def hc_macs(model: dict) -> float:
    """MACs a token of ONE sublayer's mHC: the mixes' projection, the read
    ``u`` and the write ``X'`` (the Sinkhorn rounds are not matrix work)."""
    n, d = int(model["hc_mult"]), int(model["hidden_size"])
    return n * d * (2 * n + n * n) + n * d + (n * n + n) * d


def kda_need(model: dict, traffic: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) each call of the KDA kernel needs in one layer over one
    batch: one call per prompt for its prefix (from a zero state), one per
    prompt for its suffixes (from the prefix's state). Bytes: q, k, v and g
    read and o written once at 2 bytes an element, beta a head; a state is
    float32 [heads, d, d]: the prefix call writes one, the suffix call reads
    one."""
    h, d, _ = weights.linear_shape(model)
    pre, suf = batch_lengths(traffic)
    s = len(suf) // len(pre)
    rows_suf = s * sum(suf) / len(suf)  # a prompt's suffix tokens, at the mean
    per_token_bytes = (5 * h * d + h) * BF16
    state = h * d * d * F32
    per_token_flops = recurrence_flops_per_token(model)
    calls = []
    for p in pre:
        calls.append((per_token_flops * p, per_token_bytes * p + state))
        calls.append((per_token_flops * rows_suf, per_token_bytes * rows_suf + state))
    return calls


def kda_roofline_s(model: dict, traffic: dict, peaks: dict) -> float:
    """The least time the chip could spend in the KDA kernel over one batch:
    per call the larger of FLOPs over the bf16 peak and bytes over the HBM
    rate, summed over the calls of every KDA layer."""
    return n_layers(model, weights.is_linear_layer) * sum(
        max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
        for f, b in kda_need(model, traffic)
    )


def flash_need(model: dict, traffic: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) each flash-kernel call of one latent layer needs over
    one batch: one causal call per prompt for its prefix, one prefix-shared
    call per prompt for its suffixes. The latent keys and values are expanded
    before the kernel, so it sees as many key heads as query heads, qk and v
    of their own widths. FLOPs: QK^T and PV over the keys a query can see
    (``attended_keys``, prompt by prompt), 2 a MAC. Bytes: q read, the keys
    and values read once (the suffix call reads its prompt's prefix keys and
    values once for all its suffixes), the output written, in bfloat16."""
    h, dn, dv = (int(model[k]) for k in ("num_attention_heads", "qk_nope_head_dim", "v_head_dim"))
    pre, suf = batch_lengths(traffic)
    s = len(suf) // len(pre)
    # a prompt's s suffixes at the batch's mean: which suffixes a prompt gets
    # changes with the seed, their tokens and their own causal keys do not
    rows = s * sum(suf) / len(suf)
    own_keys = s * sum(x * (x + 1) / 2 for x in suf) / len(suf)
    per_key = 2.0 * h * (dn + dv)
    kv_row, qo_row = h * (dn + dv) * BF16, h * (dn + dv) * BF16
    calls = []
    for p in pre:
        calls.append((per_key * p * (p + 1) / 2, p * (qo_row + kv_row)))
        calls.append((per_key * (rows * p + own_keys), rows * (qo_row + kv_row) + p * kv_row))
    return calls


def flash_roofline_s(model: dict, traffic: dict, peaks: dict) -> float:
    """The least time the chip could spend in the flash kernels over one
    batch: per call the larger of FLOPs over the bf16 peak and bytes over the
    HBM rate, summed over the calls of every latent layer."""
    return n_layers(model, lambda m, i: not weights.is_linear_layer(m, i)) * sum(
        max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
        for f, b in flash_need(model, traffic)
    )


def needed_flops(model: dict, traffic: dict, held_assignments: float) -> float:
    """FLOPs one batch needs (2 per MAC): each mixer's projections, mHC around
    both sublayers, the latent layers' causal scores, the KDA layers'
    recurrence at its own count, the dense MLPs, the router at its whole
    width and the shared expert, ``held_assignments`` routed token-expert
    pairs through a held expert's SwiGLU (the account's count, real tokens
    only), and the head on the scored rows (one per suffix) over the
    vocabulary held."""
    d, n = int(model["hidden_size"]), int(model["num_hidden_layers"])
    f_moe = int(model["moe_intermediate_size"])
    pre, suf = batch_lengths(traffic)
    tokens = sum(pre) + sum(suf)
    h, dn, dv = (int(model[k]) for k in ("num_attention_heads", "qk_nope_head_dim", "v_head_dim"))
    total = 0.0
    for i in range(n):
        macs = 2 * hc_macs(model)
        if weights.is_linear_layer(model, i):
            macs += kda_projection_macs(model)
            total += tokens * recurrence_flops_per_token(model)
        else:
            macs += latent_projection_macs(model)
            total += 2.0 * attended_keys(pre, suf) * h * (dn + dv)
        if weights.is_moe_layer(model, i):
            macs += d * weights.router_width(model)
            macs += 3 * d * f_moe * int(model.get("n_shared_experts") or 0)
        else:
            macs += 3 * d * int(model["intermediate_size"])
        total += 2.0 * tokens * macs
    total += 2.0 * held_assignments * 3 * d * f_moe
    return total + 2.0 * d * int(model["vocab_size"]) * len(suf)
