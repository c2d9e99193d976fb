"""Driver ``score_closed_family``: ``score_closed``'s closed loop of scoring
batches through ``runtime.orchestration.run_prompts``, for a configuration
whose seeded weights, plain reference and FLOP counts live under
``benchmark/families/<model_type>/`` (``score_closed`` itself is bound to the
``deepseek_v3`` modules at the benchmark's top level).

The window, the rate, the ``run`` dict and the sample are ``score_closed``'s:
what needs no edit is imported from it, and ``setup``, ``run`` and ``check``
are copies that take the family's modules (PERF.md section 7 asks the next
``benchmark`` issue to merge them). ``release`` also drops the residency
tier's pins, so the float32 reference does not run beside them.
"""

from __future__ import annotations

import importlib
import os
import time

import numpy as np

from benchmark import check as chk
from benchmark import trace_reduce, traffic as tr
from benchmark.drivers import score_closed
from benchmark.drivers.score_closed import (  # noqa: F401  (the driver's interface)
    program_config,
    sample,
    sample_indices,
)


def family_module(model: dict, name: str):
    """``weights`` or ``reference`` of the configuration's family:
    ``benchmark/families/<model_type>/<name>.py``."""
    return importlib.import_module(f"benchmark.families.{model['model_type']}.{name}")


def setup(ctx) -> dict:
    """Link rate, weights on disk, program config; everything before the
    first warm-up batch. A program that cannot parse the configuration fails
    here, before anything is written."""
    import jax
    from flexible_llm_sharding_tpu.config import LlamaConfig

    log = ctx["log"]
    model, seed = ctx["model"], ctx["seed"]
    weights = family_module(model, "weights")
    LlamaConfig.from_hf_config(weights.hf_config(model))
    t0 = time.monotonic()
    link = score_closed.measure_link_gbps(ctx["device"])
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
    score = score_closed._fault_wrap(
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
    traced = 0

    bytes0 = ex_mod.process_streamed_bytes()
    cache0 = score_closed._cache_stats(cfg)
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
        traced += tracing
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
        "traced_batches": traced,
    }
    cache1 = score_closed._cache_stats(cfg)
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
    reference runs: the host cache, the compiled programs, and the residency
    tier's pins (most of the chip's memory since PR 26)."""
    from flexible_llm_sharding_tpu.runtime import residency

    reset = getattr(residency, "reset_process_tier", None)
    if reset is not None:
        reset()
    score_closed.release(ctx, run)


def check(ctx, run, **variant) -> tuple[bool, dict]:
    """The family's plain float32 reference over the sample; every row
    compared. ``variant``: the reference's own controls (calibration)."""
    seqs, probs = sample(ctx, run)
    logits = family_module(ctx["model"], "reference").forward_rows(
        ctx["model"], ctx["seed"], seqs, **variant)
    numbers = chk.compare(probs, logits)
    rows = sum(len(s["rows"]) for s in seqs)
    return chk.verdict(numbers, ctx["traffic"]["limits"], rows_min=rows)
