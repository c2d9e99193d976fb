"""Operations and bytes the model NEEDS, from shapes alone. The yardstick's
own arithmetic: a later PR may change what the program computes (it computes
all E experts today), not what a token needs (k routed + the shared ones)."""

from __future__ import annotations


def _attn_proj_params(m: dict) -> int:
    d, h = int(m["hidden_size"]), int(m["num_attention_heads"])
    dn, dr = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"])
    dv, kvr = int(m["v_head_dim"]), int(m["kv_lora_rank"])
    return d * h * (dn + dr) + d * (kvr + dr) + kvr * h * (dn + dv) + h * dv * d


def expert_term_params(m: dict, all_experts: bool = False) -> int:
    """MAC count of one expert layer's MLP per token: routed (k needed, or
    all E as the program computes them) + shared experts + router."""
    d, f = int(m["hidden_size"]), int(m["moe_intermediate_size"])
    e, k = int(m["n_routed_experts"]), int(m["num_experts_per_tok"])
    ns = int(m.get("n_shared_experts") or 0)
    return ((e if all_experts else k) + ns) * 3 * d * f + d * e


def layer_flops_per_token(m: dict, moe: bool, context: float) -> float:
    d, h = int(m["hidden_size"]), int(m["num_attention_heads"])
    dn, dr, dv = int(m["qk_nope_head_dim"]), int(m["qk_rope_head_dim"]), int(m["v_head_dim"])
    mlp = expert_term_params(m) if moe else 3 * d * int(m["intermediate_size"])
    scores = context * h * (dn + dr + dv)  # QK^T and PV MACs at this context
    return 2.0 * (_attn_proj_params(m) + mlp + scores)


def needed_flops(m: dict, tokens: float, mean_context: float, head_rows: float) -> float:
    """FLOPs a forward pass over ``tokens`` tokens needs (2 per MAC): every
    layer at the mean attended context, plus the head on ``head_rows`` rows
    (scoring reads one row per suffix; decoding one per token)."""
    n = int(m["num_hidden_layers"])
    first = int(m.get("first_k_dense_replace", 0))
    per_tok = sum(layer_flops_per_token(m, i >= first, mean_context) for i in range(n))
    head = 2.0 * int(m["hidden_size"]) * int(m["vocab_size"])
    return per_tok * tokens + head * head_rows
