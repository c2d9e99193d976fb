"""Driver ``score_closed``: a closed loop of scoring batches through
``runtime.orchestration.run_prompts`` (what ``cli.main`` calls), with the
configuration the CLI's own parser resolves from no flag but the model path.

The window holds whole batches: it closes when the first batch completes at
or after ``--seconds``. The rate counts all real prefix and suffix tokens of
all those batches over the window's real length.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark import check as chk
from benchmark import reference, trace_reduce, traffic as tr, weights


def program_config(model_dir: str, rehearsal: bool):
    """``FrameworkConfig`` as ``cli.main`` builds it from an argv that names
    the model and nothing else: defaults and ``auto`` resolutions are what is
    measured. The rehearsal alone turns the kernels on (interpret mode)."""
    from flexible_llm_sharding_tpu import cli

    argv = ["--model_path", model_dir, "--prompt_pickle", "-", "--output_file", "-"]
    if rehearsal:
        argv += ["--use_pallas", "true"]
    return cli.config_from_args(cli.build_parser().parse_args(argv))


def _fault_wrap(fn, fault: str):
    """Tests only (``--fault``): break the timed path underneath."""
    if fault == "alter_answer":
        def broken(*a, **k):
            out = fn(*a, **k)
            out = [np.array(o) for o in out]
            for o in out:  # every prompt's first answer shifted by one token id
                o[0] = np.roll(o[0], 1, axis=-1)
            return out
        return broken
    if fault:
        raise SystemExit(f"unknown fault {fault!r} for score_closed")
    return fn


def setup(ctx) -> dict:
    """Link rate, weights on disk, program config; everything before the
    first warm-up batch."""
    import jax

    log = ctx["log"]
    model, seed = ctx["model"], ctx["seed"]
    t0 = time.monotonic()
    link = measure_link_gbps(ctx["device"])
    t1 = time.monotonic()
    model_dir = os.path.join(ctx["work"], "model")
    wrote = weights.write_model(model, seed, model_dir)
    jax.clear_caches()  # the generators' programs are done with
    t2 = time.monotonic()
    log(f"set-up: link {link:.2f} GB/s ({t1 - t0:.1f} s); weights {wrote['bytes_model'] / 1e9:.2f} GB "
        f"model, {wrote['bytes_written'] / 1e9:.2f} GB written ({t2 - t1:.1f} s); "
        f"host {os.cpu_count()} cores")
    return {"link_gbps": link, "model_dir": model_dir, "wrote": wrote,
            "cfg": program_config(model_dir, ctx["rehearsal"])}


def measure_link_gbps(device, mb: int = 256) -> float:
    """Host->HBM rate: one timed ``device_put`` of ``mb`` MB to completion,
    after a warm one of the same shape (copy of the program's
    ``measure_host_to_hbm_gbps``)."""
    import jax

    buf = np.ones((mb, 1024, 256), np.float32)
    a = jax.device_put(buf, device)
    jax.device_get(a.sum())
    t0 = time.perf_counter()
    a = jax.device_put(buf, device)
    jax.device_get(a.sum())
    dt = time.perf_counter() - t0
    del a
    return buf.nbytes / 1e9 / dt


def _cache_stats(cfg) -> dict | None:
    from flexible_llm_sharding_tpu.runtime import hostcache

    cache = hostcache.cache_for(cfg)
    return dict(cache.stats()) if cache is not None else None


def run(ctx) -> dict:
    import jax
    from flexible_llm_sharding_tpu.runtime import executor as ex_mod
    from flexible_llm_sharding_tpu.runtime import orchestration

    log, span, compiles = ctx["log"], ctx["span"], ctx["compiles"]
    model, traffic, seed = ctx["model"], ctx["traffic"], ctx["seed"]
    vocab = int(model["vocab_size"])
    tok = tr.WordIdTokenizer(vocab)
    st = setup(ctx)
    cfg = st["cfg"]
    score = _fault_wrap(
        lambda prompts: orchestration.run_prompts(cfg, prompts, tokenizer=tok), ctx["fault"]
    )

    # Warm-up: the window's own shapes (every batch has the same multiset of
    # lengths), through the window's own call. Indices from 10**6: a stream of
    # batches the window never sees.
    for w in range(int(traffic.get("warmup_batches", 1))):
        t0 = time.monotonic()
        score(tr.make_batch(traffic, vocab, seed, 10**6 + w))
        log(f"warm-up batch {w}: {time.monotonic() - t0:.2f} s")
    compiles_setup, compile_s_setup = compiles.count, compiles.seconds

    pick_rng = np.random.default_rng([seed, 0xC4EC])
    trace_from, trace_n = 1, int(traffic.get("trace_batches", 4))
    trace_dir = os.path.join(ctx["work"], "trace")
    tracing = False
    t_trace0 = t_trace1 = None

    bytes0 = ex_mod.process_streamed_bytes()
    cache0 = _cache_stats(cfg)
    kept = []  # (batch index, prompt index, prompt, scores)
    batch_walls, batch_ends, tokens = [], [], 0
    t_open = time.monotonic()
    setup_s = t_open - ctx["t_process_start"]
    i = 0
    while True:
        if ctx["trace"] and i == trace_from:
            trace_reduce.start(trace_dir)
            tracing, t_trace0 = True, time.monotonic()  # after the profiler is up
        with span("batch.prepare"):
            prompts = tr.make_batch(traffic, vocab, seed, i)
        tb = time.monotonic()
        with span("batch.run"):
            scores = score(prompts)
        te = time.monotonic()
        batch_walls.append(te - tb)
        batch_ends.append(te)
        tokens += tr.count_tokens(tok, prompts)
        j = int(pick_rng.integers(len(prompts)))
        for jj in (range(len(prompts)) if ctx.get("keep_all") else [j]):
            kept.append((i, jj, prompts[jj], np.asarray(scores[jj])))
        i += 1
        if tracing and i == trace_from + trace_n:
            t_trace1 = time.monotonic()
            jax.profiler.stop_trace()
            tracing = False
        if te - t_open >= ctx["seconds"]:
            break
    if tracing:
        t_trace1 = time.monotonic()
        jax.profiler.stop_trace()
    t_close = batch_ends[-1]
    window_s = t_close - t_open
    counters = {
        "batches": i,
        "tokens": tokens,
        "streamed_bytes": ex_mod.process_streamed_bytes() - bytes0,
        "link_gbps": st["link_gbps"],
        "batch_walls": batch_walls,
        "window_s": window_s,
    }
    cache1 = _cache_stats(cfg)
    if cache0 is not None and cache1 is not None:
        counters["host_cache_hits"] = cache1["hits"] - cache0["hits"]
        counters["host_cache_misses"] = cache1["misses"] - cache0["misses"]
    out = {
        "end_to_end": {"score_tokens_per_s": tokens / window_s, "setup_s": setup_s},
        "attempted": i * int(traffic["prompts"]), "failed": 0,
        "compiles_setup": compiles_setup, "compile_s_setup": compile_s_setup,
        "compiles_window": compiles.count - compiles_setup,
        "counters": counters, "kept": kept, "tokenizer": tok,
        "info": {"batches": i, "window_s": window_s, "tokens": tokens,
                 "link_gbps": st["link_gbps"], "bytes_written": st["wrote"]["bytes_written"]},
    }
    if t_trace0 is not None:
        out["trace"] = trace_reduce.reduce_dir(trace_dir, window_s=t_trace1 - t_trace0)
    log(f"window: {i} batches, {tokens} tokens, {window_s:.2f} s, "
        f"median batch {float(np.median(batch_walls)):.3f} s")
    log("batch ends: " + " ".join(f"{t - t_open:.3f}" for t in batch_ends))
    return out


def release(ctx, run) -> None:
    """Free what the program holds on the device and the host before the
    reference runs."""
    import jax
    from flexible_llm_sharding_tpu.runtime import hostcache

    hostcache.reset_process_cache()
    gc.collect()
    jax.clear_caches()


def sample_indices(ctx, run) -> list[int]:
    """Which of the kept prompts are compared: a seeded sample (one was kept
    per batch, chosen from the seed; the last batch's is always in)."""
    kept = run["kept"]
    n = min(int(ctx["traffic"].get("check_prompts", 8)), len(kept))
    rng = np.random.default_rng([ctx["seed"], 0x5A3])
    if len(kept) < 2:
        return [0]
    return sorted(set(rng.choice(len(kept) - 1, size=n - 1, replace=False).tolist())
                  | {len(kept) - 1})


def sample(ctx, run) -> tuple[list[dict], list[np.ndarray]]:
    """The sampled prompts as reference sequences at their own lengths,
    beside the program's probability rows."""
    traffic = ctx["traffic"]
    tok = run["tokenizer"]
    kept = run["kept"]
    idx = sample_indices(ctx, run)
    s = int(traffic["suffixes"])
    longest = max(tr.quantile_lengths(traffic["prefix_tokens"], int(traffic["prompts"]))) + 1
    longest += s * max(tr.quantile_lengths(traffic["suffix_tokens"], int(traffic["prompts"]) * s))
    pad_to = tr.bucket(longest, 64)
    seqs, probs = [], []
    for k in idx:
        _, _, (prefix, suffixes), scores = kept[k]
        pids = tok(prefix)["input_ids"]
        sids = [x[1:] for x in tok(list(suffixes))["input_ids"]]
        seqs.append(reference.scoring_sequence(pids, sids, pad_to))
        probs.append(np.asarray(scores)[:, 0, :])
    return seqs, probs


def check(ctx, run) -> tuple[bool, dict]:
    """The plain float32 reference over the sample; every row compared."""
    seqs, probs = sample(ctx, run)
    logits = reference.forward_rows(ctx["model"], ctx["seed"], seqs)
    numbers = chk.compare(probs, logits)
    rows = sum(len(s["rows"]) for s in seqs)
    return chk.verdict(numbers, ctx["traffic"]["limits"], rows_min=rows)
