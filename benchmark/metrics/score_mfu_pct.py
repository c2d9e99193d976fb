"""Whole step's share of the chip's peak: FLOPs the scored tokens NEED (k
routed + shared experts, attention with its scores at the mean attended
context, the head on the scored rows only) per second of the window, over the
chip's bf16 peak."""

from benchmark import flops, traffic as tr


def read(run):
    ctx, c = run["ctx"], run["counters"]
    if ctx["peaks"] is None or not c.get("tokens"):
        return None
    t = ctx["traffic"]
    n, s = int(t["prompts"]), int(t["suffixes"])
    pre = [x + 1 for x in tr.quantile_lengths(t["prefix_tokens"], n)]
    suf = tr.quantile_lengths(t["suffix_tokens"], n * s)
    # mean attended context over a batch's tokens: a prefix token at position
    # i attends to i+1; a suffix token to its prefix and its own start.
    ctx_sum = sum(p * (p + 1) / 2 for p in pre)
    ctx_sum += sum(pre) / n * sum(suf) + sum(x * (x + 1) / 2 for x in suf)
    tokens_batch = sum(pre) + sum(suf)
    mean_context = ctx_sum / tokens_batch
    need = flops.needed_flops(ctx["model"], tokens_batch, mean_context, head_rows=n * s)
    rate = c["batches"] / c["window_s"]
    return 100.0 * need * rate / ctx["peaks"]["bf16_flops"]
