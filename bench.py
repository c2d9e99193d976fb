"""Benchmark phases: layer-streaming scoring on whatever device JAX reports.

Measures the framework's core capability — streaming a model through the chip
shard-by-shard while scoring a prompt batch (the reference's headline feature,
``/root/reference/utils.py:226-302``) — and reports tokens/sec with overlapped
weight prefetch. ``vs_baseline`` is the speedup over the *same* executor run
with ``prefetch_depth=0``, i.e. the reference's fully serialized
load-then-compute schedule (``/root/reference/utils.py:228-233``).

The device is whatever ``jax.devices()`` reports: a backend that fails to
come up is an error, never a reason to carry on elsewhere. A CPU run is what
``JAX_PLATFORMS=cpu`` in the environment asks for (the tests and
``scripts/perf_gate.py`` do), and its ``platform`` key says so; a number from
such a run is a count or a CPU ratio, never a device metric. The on-chip
proof that the system starts is ``chip_smoke.py``; this file's wholesale
replacement by a real benchmark is ROADMAP D1.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "tokens_per_sec": N, "tokens_per_sec_per_chip": N, "peak_hbm_gb": N,
   "platform": ..., "pallas_speedup_4k": N, "decode_speedup_4tok": N,
   "mfu": N, "model_flops_per_token": N, "host_to_hbm_gbps": N}

decode_speedup_4tok: KV-cache decode vs the reference's full-recompute
generation algorithm on the same workload (its per-token scaling cliff,
/root/reference/main.py:63-90).

mfu: achieved model-FLOPs/sec over the chip's peak bf16 FLOP/s
(utils/metrics.py chip_peak_flops) — for a weight-streaming workload this is
transfer-bound and should be read against host_to_hbm_gbps.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
import zlib

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.join(ROOT, "bench_tmp")

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _init_jax():
    """``jax.devices()``, plainly: the backend the environment names comes up
    or the bench fails. The persistent compile cache is placed first
    (``utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed in-checkout path)."""
    import jax

    from flexible_llm_sharding_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    return jax, jax.devices()



class BenchTokenizer:
    """Deterministic word-hash tokenizer (no model assets needed)."""

    BOS, EOS, VOCAB = 1, 2, 32000

    eos_token = "</s>"
    pad_token = "</s>"
    pad_token_id = EOS
    padding_side = "right"

    def _one_id(self, w: str) -> int:
        # Round-trip for decode()'s output, so the generation loop's
        # string-rebuild semantics retokenize generated tokens faithfully
        # (needed for the recompute-vs-kv-cache comparison to be apples to
        # apples).
        if w.startswith("tok") and w[3:].isdigit():
            return int(w[3:]) % self.VOCAB
        # crc32, not hash(): Python's hash() is salted per process, which
        # would vary token ids (and thus timings) between invocations.
        return 3 + (zlib.crc32(w.encode()) % (self.VOCAB - 3))

    def _ids(self, text: str) -> list[int]:
        return [self.BOS] + [self._one_id(w) for w in text.split()]

    def decode(self, ids) -> str:
        if np.ndim(ids) == 0:
            ids = [int(ids)]
        return "".join(f" tok{int(i)}" for i in ids)

    def __call__(self, text, max_length=None, padding=False, **kw):
        if isinstance(text, str):
            ids = self._ids(text)[:max_length]
            return {"input_ids": ids}
        batch = [self._ids(t)[:max_length] for t in text]
        if padding:
            width = max(len(b) for b in batch)
            batch = [b + [self.pad_token_id] * (width - len(b)) for b in batch]
        return {"input_ids": batch}


def make_model(jax, cfg_kwargs: dict) -> str:
    """Build (once, cached) a synthetic per-layer checkpoint under bench_tmp."""
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama
    from flexible_llm_sharding_tpu.utils.checkpoint import save_params

    tag = "-".join(str(v) for v in cfg_kwargs.values())
    out = os.path.join(BENCH_DIR, f"model-{tag}")
    if os.path.exists(os.path.join(out, "config.json")):
        return out
    log(f"building synthetic checkpoint at {out} ...")
    cfg = LlamaConfig(**cfg_kwargs)
    import jax.numpy as jnp

    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    save_params(jax.tree.map(np.asarray, params), out, cfg)
    return out


def make_prompts(n: int, prefix_words: int, suffix_words: int, n_suffix: int):
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(5000)]

    def text(k):
        return " ".join(rng.choice(words, size=k))

    return [
        (text(prefix_words), tuple(text(suffix_words) for _ in range(n_suffix)))
        for _ in range(n)
    ]


def _count_pass_tokens(tok, prompts) -> int:
    """Tokens processed per full-model pass: every prompt runs prefix + all
    suffixes (each suffix minus its shared leading token) through every
    layer — the SAME accounting as the CLI's tokens_processed
    (runtime/tokenization.py count_tokens). One helper shared by the toy
    and GB benches so the counting convention cannot desync."""
    ids = [tok(p)["input_ids"] for p, _ in prompts]
    sids = [tok(list(s), padding=False)["input_ids"] for _, s in prompts]
    return sum(len(i) for i in ids) + sum(
        len(x) - 1 for s in sids for x in s
    )


def run_once(cfg_obj, prompts, tokenizer):
    from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor

    ex = StreamingExecutor(cfg_obj, tokenizer=tokenizer)
    t0 = time.perf_counter()
    scores = ex(prompts)
    wall = time.perf_counter() - t0
    return scores, wall, ex


def bench_pallas(jax, result: dict) -> None:
    """Flash-vs-XLA attention at a 7B-shaped 4k-context shape; the number
    substantiating the Pallas kernels' perf claim (ops/pallas_attention.py)."""
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.ops.attention import prefix_shared_attention
    from flexible_llm_sharding_tpu.ops.pallas_attention import (
        flash_prefix_shared_attention,
        supports,
    )

    s, ls, lp = 4, 64, 4032  # one 4096-token bucket: shared prefix + suffixes
    n_q = n_kv = 8  # one chip's worth of 7B heads is BW-equivalent per-head
    hd = 128
    if not supports(n_q, n_kv, hd, ls, lp):
        return
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (s, ls, n_q, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (lp, n_kv, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (lp, n_kv, hd), jnp.bfloat16)
    ksfx = jax.random.normal(ks[3], (s, ls, n_kv, hd), jnp.bfloat16)
    vsfx = jax.random.normal(ks[4], (s, ls, n_kv, hd), jnp.bfloat16)
    plen = jnp.int32(lp - 17)

    def timed(fn, iters=10):
        jax.device_get(fn())  # compile + drain (host read-back)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.device_get(out)
        return (time.perf_counter() - t0) / iters

    t_xla = timed(lambda: prefix_shared_attention(q, kp, vp, ksfx, vsfx, plen))
    t_flash = timed(
        lambda: flash_prefix_shared_attention(q, kp, vp, ksfx, vsfx, plen)
    )
    log(f"attention 4k: xla={t_xla*1e3:.2f}ms flash={t_flash*1e3:.2f}ms")
    result["pallas_speedup_4k"] = round(t_xla / t_flash, 3)

    # MLA shapes (DeepSeek-V3: qk 192, v 128 — distinct dims ride the flash
    # path since r4): the kernel pays a 256-lane pad on QK^T but never
    # materialises the [Lq, Lk] scores the XLA op spills at 4k.
    hd_qk, hd_v = 192, 128
    if supports(n_q, n_kv, hd_qk, ls, lp, v_dim=hd_v):
        ks2 = jax.random.split(jax.random.PRNGKey(1), 5)
        qm = jax.random.normal(ks2[0], (s, ls, n_q, hd_qk), jnp.bfloat16)
        kpm = jax.random.normal(ks2[1], (lp, n_kv, hd_qk), jnp.bfloat16)
        vpm = jax.random.normal(ks2[2], (lp, n_kv, hd_v), jnp.bfloat16)
        ksm = jax.random.normal(ks2[3], (s, ls, n_kv, hd_qk), jnp.bfloat16)
        vsm = jax.random.normal(ks2[4], (s, ls, n_kv, hd_v), jnp.bfloat16)
        t_xla_m = timed(
            lambda: prefix_shared_attention(qm, kpm, vpm, ksm, vsm, plen)
        )
        t_flash_m = timed(
            lambda: flash_prefix_shared_attention(qm, kpm, vpm, ksm, vsm, plen)
        )
        log(
            f"MLA attention 4k: xla={t_xla_m*1e3:.2f}ms "
            f"flash={t_flash_m*1e3:.2f}ms"
        )
        result["pallas_mla_speedup_4k"] = round(t_xla_m / t_flash_m, 3)


def bench_decode(cfg_obj, prompts, tok, result: dict, n_tok: int = 4) -> None:
    """KV-cache decode vs the reference's full-recompute generation loop
    (``/root/reference/main.py:63-90`` — per-token cost equals full-prompt
    cost, its known scaling cliff, SURVEY.md §3.5). Same model, same
    prompts, same greedy semantics; ``decode_speedup_{n}tok`` is the wall
    ratio, the framework's headline win over the reference's algorithm."""
    import dataclasses

    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator
    from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor
    from flexible_llm_sharding_tpu.runtime.generation import generation_loop

    cfg_obj = dataclasses.replace(cfg_obj, num_gen_token=n_tok)

    # Warm BOTH paths fully (their jit shapes depend on the prompt block
    # and on n_tok), then measure — otherwise compile time amortizes over
    # the recompute path's n_tok passes but lands wholly inside the single
    # KV pass, skewing the ratio.
    ex = StreamingExecutor(cfg_obj, tokenizer=tok)
    generation_loop(ex, prompts, n_tok, tok)
    gen = DecodeGenerator(cfg_obj, tokenizer=tok)
    gen(prompts)

    t0 = time.perf_counter()
    ref_scores, _ = generation_loop(ex, prompts, n_tok, tok)
    t_recompute = time.perf_counter() - t0

    t0 = time.perf_counter()
    kv_scores, _ = gen(prompts)
    t_kv = time.perf_counter() - t0

    # Same greedy semantics -> same argmax tokens, UP TO near-ties: the two
    # paths order bf16 reductions differently (flash kernels vs fused XLA),
    # and this synthetic random-weight model's softmax is nearly flat, so a
    # sub-1e-4 probability margin can legitimately flip an argmax (measured
    # on hardware: scores agree to 7e-6 while one argmax flips on a 6e-6
    # margin). After a benign flip the two paths' contexts genuinely
    # diverge (each greedy loop feeds back its own token), so comparison of
    # that prompt stops there. A flip with a REAL margin, or a score error
    # above tolerance before any flip, is still flagged as a mismatch.
    tie_tol, err_tol = 1e-4, 1e-3
    agree, maxerr = True, 0.0
    for a, b in zip(ref_scores, kv_scores):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        am, bm = np.argmax(a, axis=-1), np.argmax(b, axis=-1)
        for s in range(a.shape[0]):  # per suffix: steps are sequential
            for t in range(a.shape[1]):
                # Contexts are identical THROUGH step t (divergence starts
                # at t+1), so the score error at the flip step still counts.
                maxerr = max(maxerr, float(np.abs(a[s, t] - b[s, t]).max()))
                if am[s, t] != bm[s, t]:
                    margin = a[s, t, am[s, t]] - a[s, t, bm[s, t]]
                    if margin > tie_tol:
                        agree = False
                    break  # contexts diverge from here; stop this suffix
    if maxerr > err_tol:
        agree = False
    log(
        f"generation {n_tok} tok: recompute={t_recompute:.2f}s "
        f"kv_cache={t_kv:.2f}s agree={agree} score_maxerr={maxerr:.2e}"
    )
    result[f"decode_speedup_{n_tok}tok"] = round(t_recompute / t_kv, 3)
    result["decode_score_maxerr"] = float(f"{maxerr:.3e}")
    if not agree:
        result["decode_argmax_mismatch"] = True

    import jax

    if jax.default_backend() == "tpu":
        # Flash decode kernel vs the XLA decode op (the production path is
        # auto = flash on TPU, so the measured `gen` above already used it;
        # this isolates the kernel's own contribution).
        gen_xla = DecodeGenerator(
            dataclasses.replace(cfg_obj, use_pallas=False), tokenizer=tok
        )
        gen_xla(prompts)  # warm/compile
        t0 = time.perf_counter()
        gen_xla(prompts)
        t_xla_dec = time.perf_counter() - t0
        log(f"decode attention: xla={t_xla_dec:.2f}s flash={t_kv:.2f}s")
        result["pallas_decode_speedup"] = round(t_xla_dec / t_kv, 3)


def bench_host_stream(result: dict, model_path: str, budget_left) -> None:
    """Host half of the weight stream, measured WITHOUT the accelerator:
    disk -> numpy -> cast -> stacked-pytree, the machinery every sweep runs
    before the host->HBM upload.

    Two paths, cold (page cache evicted via native FADV_DONTNEED) and warm:
    - zero-copy: checkpoint dtype == compute dtype; layer files mmap in and
      the pass only faults pages (one touch per 4 KiB page).
    - cast: compute dtype != stored dtype (the reference's fp16-checkpoint
      case); every byte is read and converted.
    host_readahead_speedup: the C++ readahead pool warming shard t+1 while
    shard t is cast — measured on the cold cast path, where it can overlap
    disk wait with convert CPU.
    """
    import jax
    import numpy as _np

    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        np_dtype_for,
    )
    from flexible_llm_sharding_tpu.utils import checkpoint as _ckpt
    from flexible_llm_sharding_tpu.utils.native import drop_file_cache

    cfg = LlamaConfig.from_pretrained(model_path)
    names = _ckpt.layer_names_for(cfg.num_hidden_layers, cfg.tie_word_embeddings)
    files = [
        os.path.join(model_path, f"{n}{_ckpt.LAYER_FILE_SUFFIX}") for n in names
    ]
    total_gb = sum(os.path.getsize(f) for f in files) / 1e9

    def one_pass(np_dtype, touch: bool, readahead: bool) -> float:
        # device_cast=False: this bench measures the HOST-cast pipeline
        # (the reference's fp16-checkpoint case, and the executor's
        # fallback arm) — with the default on-device cast the "cast"
        # passes would silently degenerate into zero-copy ones.
        loader = _HostShardLoader(
            model_path, names, np_dtype,
            readahead="on" if readahead else "off",
            device_cast=False,
        )
        t0 = time.perf_counter()
        for i in range(len(names)):
            if readahead and i + 1 < len(names):
                loader.warm((i + 1,))
            segs = loader.build_host_shard((i,))
            if touch:  # mmap views: fault each 4 KiB page (2048 2-byte elems)
                for leaf in jax.tree.leaves(segs):
                    a = _np.asarray(leaf)
                    a.reshape(-1).view(_np.uint8)[:: 4096].max()
            del segs
        dt = time.perf_counter() - t0
        loader.close()
        return dt

    bf16, f32 = np_dtype_for("bfloat16"), np_dtype_for("float32")
    try:
        one_pass(bf16, True, False)  # build caches / warm the lazy imports
        t = min(one_pass(bf16, True, False) for _ in range(2))
        result["host_stream_zero_copy_warm_gbps"] = round(total_gb / t, 2)
        t = min(one_pass(f32, False, False) for _ in range(2))
        result["host_stream_cast_warm_gbps"] = round(total_gb / t, 2)
        # Cold passes hit the real disk and can be slow: stop between
        # sub-measurements once they'd start starving the device phases.
        # EVERY pass re-checks that eviction succeeded — a warm pass
        # labelled cold corrupts both the gbps numbers and the speedup.
        t_cast_cold = None
        if budget_left() > 0.85 and drop_file_cache(*files):
            t_cold = one_pass(bf16, True, False)
            result["host_stream_zero_copy_cold_gbps"] = round(total_gb / t_cold, 2)
            if budget_left() > 0.8 and drop_file_cache(*files):
                t_cast_cold = one_pass(f32, False, False)
                result["host_stream_cast_cold_gbps"] = round(
                    total_gb / t_cast_cold, 2
                )
            # The readahead ratio only means something against the cast-cold
            # baseline it shares a pipeline with.
            if (
                t_cast_cold is not None
                and budget_left() > 0.75
                and drop_file_cache(*files)
            ):
                t_ra = one_pass(f32, False, True)
                result["host_readahead_speedup"] = round(t_cast_cold / t_ra, 3)
        log(
            "host stream: "
            + " ".join(
                f"{k.replace('host_stream_', '')}={result[k]}"
                for k in sorted(result)
                if k.startswith(("host_stream_", "host_readahead"))
            )
        )
    except Exception:
        log("host stream bench failed:\n" + traceback.format_exc())


def bench_host_cache(result: dict, model_path: str, budget_left, device) -> None:
    """PR 5 tentpole evidence: the host-resident shard cache and the
    on-device cast, measured over the same prepared model dir as
    bench_host_stream.

    - ``warm_sweep_speedup``: full host sweep 1 (disk read + parse +
      checksum + stack) vs sweep 2+ (cache hits) — the host-side work a
      steady-state serve sweep no longer pays.
    - ``host_cache_hit_rate``: the cache's hit rate after 3 sweeps (2/3
      with an unbounded budget; lower means the budget evicted).
    - ``device_cast_speedup``: host cast (native/numpy RNE) + upload of
      the cast bytes vs raw upload + one jitted on-chip convert, same
      shard-sized fp32->bf16 buffer. On the CPU backend the "device" is
      host memory, so only the TPU capture of this number is meaningful.
    """
    import jax
    import numpy as _np

    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        _cast_tree,
        np_dtype_for,
    )
    from flexible_llm_sharding_tpu.runtime.hostcache import HostShardCache
    from flexible_llm_sharding_tpu.utils import checkpoint as _ckpt
    from flexible_llm_sharding_tpu.utils.native import convert_array

    cfg = LlamaConfig.from_pretrained(model_path)
    names = _ckpt.layer_names_for(cfg.num_hidden_layers, cfg.tie_word_embeddings)
    try:
        cache = HostShardCache(budget_bytes=8 << 30)
        loader = _HostShardLoader(
            model_path, names, np_dtype_for("bfloat16"), host_cache=cache
        )
        sweeps = []
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(len(names)):
                loader.build_host_shard((i,))
            sweeps.append(time.perf_counter() - t0)
            # Warm sweeps are fast, but sweep 1 of a multi-GB dir is a
            # full read: stop at 2 sweeps (enough for the ratio) when the
            # deadline is running out, like bench_host_stream's cold legs.
            if len(sweeps) >= 2 and budget_left() <= 0.75:
                break
        loader.close()
        if len(sweeps) >= 2:
            warm = min(sweeps[1:])
            if warm > 0:
                result["warm_sweep_speedup"] = round(sweeps[0] / warm, 3)
        result["host_cache_hit_rate"] = cache.stats()["hit_rate"]
        if budget_left() <= 0.7:
            log("host cache bench: budget low, skipping cast arms")
            return

        # On-chip vs host cast over one shard's worth of bytes (fp32 ->
        # bf16, the widest win: half the link bytes AND no host pass).
        bf16 = np_dtype_for("bfloat16")
        src = _np.random.default_rng(0).standard_normal(
            (64, 1024, 1024 // 4), dtype=_np.float32
        )

        def host_arm() -> None:
            out = convert_array(src, bf16)
            if out is None:
                out = src.astype(bf16)
            jax.block_until_ready(jax.device_put(out, device))

        def dev_arm() -> None:
            jax.block_until_ready(
                _cast_tree(jax.device_put(src, device), "bfloat16")
            )

        host_arm(), dev_arm()  # warm transfers + compile
        t_host = min(_timed(host_arm) for _ in range(2))
        t_dev = min(_timed(dev_arm) for _ in range(2))
        if t_dev > 0:
            result["device_cast_speedup"] = round(t_host / t_dev, 3)
        log(
            f"host cache: warm_sweep_speedup={result.get('warm_sweep_speedup')} "
            f"hit_rate={result.get('host_cache_hit_rate')} "
            f"device_cast_speedup={result.get('device_cast_speedup')}"
        )
    except Exception:
        log("host cache bench failed:\n" + traceback.format_exc())


def bench_residency(
    result: dict, model_path: str, prompts, tok, budget_left, fw
) -> None:
    """PR 6 tentpole evidence: the device residency tier — pin roughly half
    the model's layers in (device) memory, stream only the rest.

    - ``partial_residency_speedup``: full streaming sweep vs the same sweep
      with the pin tier active (warm: pins already loaded), rotation-paired
      back-to-back like the hostcache phase so link drift cancels. Both
      arms run with the host shard cache OFF, so the ratio isolates the
      pin tier's own saving (skipped disk read + parse + checksum + stack
      + upload for the pinned layers).
    - ``pinned_fraction``: the planner's pinned bytes over the model's
      total streamed bytes at that budget — the denominator of the claim
      ("a K% pin cut the sweep by ~K% of its stream cost"). Recorded as
      0.0 when the pin arm's executor stats show the runtime tier never
      engaged.
    """
    import dataclasses

    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.runtime import residency
    from flexible_llm_sharding_tpu.utils import checkpoint as _ckpt

    try:
        mc = LlamaConfig.from_pretrained(model_path)
        names = _ckpt.layer_names_for(
            mc.num_hidden_layers, tie_word_embeddings=False
        )
        sizes = residency.layer_stream_bytes(
            model_path, names, mc.tie_word_embeddings
        )
        total = sum(sizes.values())
        budget_gb = (total * 0.5) / 1e9
        plan = residency.plan_residency(
            model_path, names, int(budget_gb * 1e9), mc.tie_word_embeddings
        )
        # The streaming arm pins nothing (the default would, on a chip).
        base = dataclasses.replace(fw(None), host_cache_gb=0.0, hbm_pin_gb=0.0)
        pin = dataclasses.replace(base, hbm_pin_gb=budget_gb)
        residency.reset_process_tier()
        sub = prompts[: min(4, len(prompts))]
        run_once(base, sub, tok)  # warm/compile
        run_once(pin, sub, tok)  # warm; this sweep seats the pins
        ratios = []
        for i in range(2):
            _, w_stream, _ = run_once(base, sub, tok)
            _, w_pin, ex_pin = run_once(pin, sub, tok)
            ratios.append(w_stream / w_pin)
            log(
                f"residency pair {i}: stream={w_stream:.2f}s "
                f"pinned={w_pin:.2f}s ratio={ratios[-1]:.3f}"
            )
            if budget_left() < 0.7:
                log("  residency pair budget exhausted; stopping reps")
                break
        # Recorded ONLY next to a completed speedup measurement: a phase
        # that dies mid-run must not leave an orphaned pinned_fraction for
        # best-promotion to pair with someone else's speedup.
        _ratio_stats(result, "partial_residency_speedup", ratios)
        # The fraction reports the PLANNER's ratio, but only when the
        # RUNTIME tier actually engaged in the pin arm — nonzero resident
        # bytes AND saved link bytes in the executor's own stats (both
        # keys exist only when a live tier was attached). The perf gate
        # leans on this as its tier-disengaged detector, so a locally
        # computed plan ratio must never mask a run that silently
        # streamed everything.
        engaged = (
            float(ex_pin.stats.get("pinned_bytes") or 0.0) > 0
            and float(ex_pin.stats.get("stream_bytes_saved") or 0.0) > 0
        )
        result["pinned_fraction"] = (
            round(plan.pinned_fraction, 3) if engaged else 0.0
        )
        log(
            f"residency: speedup={result['partial_residency_speedup']} "
            f"pinned_fraction={result['pinned_fraction']}"
        )
    except Exception:
        log("residency bench failed:\n" + traceback.format_exc())
    finally:
        # Drop the pins so the later phases' memory/throughput numbers
        # aren't measured next to a half-resident model.
        residency.reset_process_tier()


def bench_mixedprec(
    result: dict, model_path: str, prompts, tok, budget_left, fw
) -> None:
    """Mixed-precision streaming evidence (ISSUE 14 tentpole): a
    sensitivity-planned int4/int8/bf16 checkpoint must cut the bytes each
    sweep moves over the host->HBM link vs uniform bf16, without drifting
    past the plan's own declared divergence cap.

    - ``mixedprec_bytes_saved_frac``: 1 - (mixed streamed bytes / bf16
      streamed bytes) over identical sweeps, read from the executors' OWN
      ``streamed_bytes`` stats — structural and timing-free (byte
      counters, not walls), so the perf gate holds a hard floor on it.
    - ``mixedprec_divergence``: mean next-token KL of the mixed stream's
      scores vs the bf16 stream's — ASSERTED under the plan's declared
      cap before anything is recorded, and the plan's bf16 layers are
      asserted bit-identical to the uniform-bf16 source files. A phase
      that can't prove quality must not report bandwidth.
    """
    import dataclasses

    from flexible_llm_sharding_tpu.runtime import precisionplan as pp
    from flexible_llm_sharding_tpu.runtime import residency as _res
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.utils import checkpoint as _ckpt

    try:
        if budget_left() <= 0.2:
            # The probe alone is 2 forwards per layer on the calibration
            # batch; a nearly-spent deadline must leave its remaining
            # time to the later phases.
            log("mixedprec bench: budget exhausted, skipping")
            return
        mc = LlamaConfig.from_pretrained(model_path)
        names = _ckpt.layer_names_for(
            mc.num_hidden_layers, mc.tie_word_embeddings
        )
        baseline = sum(
            _res.layer_stream_bytes(
                model_path, names, mc.tie_word_embeddings
            ).values()
        )
        # 60% of the uniform-bf16 sweep: deep enough that the planner
        # provably engages int8/int4 (>= 40% savings — the gate floor
        # derives from here), shallow enough that the most sensitive
        # layers stay bf16 for the bit-identity half of the claim.
        calib = prompts[:1]
        plan = pp.build_plan(
            model_path, calib, tok, bytes_budget=int(baseline * 0.60)
        )
        mixed_dir = os.path.join(BENCH_DIR, "model-mixedprec")
        if os.path.exists(mixed_dir):
            import shutil

            shutil.rmtree(mixed_dir)
        _ckpt.requantize_native(model_path, mixed_dir, plan=plan)

        # bf16 layers bit-identical to the uniform-bf16 source, tensor
        # for tensor (requantize's bf16 arm is the same cast rule the
        # uniform baseline was stored with).
        bf16_layers = [n for n, d in plan.layers if d == "bf16"]
        for name in bf16_layers:
            a = _ckpt._mmap_safetensors(
                _ckpt.layer_file_for(model_path, name, mc.tie_word_embeddings)
            )
            b = _ckpt._mmap_safetensors(
                _ckpt.layer_file_for(mixed_dir, name, mc.tie_word_embeddings)
            )
            assert set(a) == set(b), f"{name}: bf16 layer tensor set drifted"
            for k in a:
                assert np.array_equal(
                    np.asarray(a[k]).view(np.uint8),
                    np.asarray(b[k]).view(np.uint8),
                ), f"{name}/{k}: bf16 layer not bit-identical to uniform bf16"

        if budget_left() <= 0.1:
            log("mixedprec bench: budget low after probe, skipping runs")
            return
        # Identical sweeps, byte counters from the executors themselves.
        base_cfg = dataclasses.replace(fw(None), host_cache_gb=0.0)
        mixed_cfg = dataclasses.replace(base_cfg, model_path=mixed_dir)
        sub = prompts[: min(2, len(prompts))]
        scores_b, _, ex_b = run_once(base_cfg, sub, tok)
        scores_m, _, ex_m = run_once(mixed_cfg, sub, tok)
        bytes_b = float(ex_b.stats["streamed_bytes"])
        bytes_m = float(ex_m.stats["streamed_bytes"])
        assert bytes_b > 0 and bytes_m > 0

        # Quality gate BEFORE recording: the mixed stream's next-token
        # distributions vs the bf16 stream's, under the plan's declared
        # cap (pp.kl_divergence is the probe's own definition).
        divs = [
            pp.kl_divergence(b[s, 0][None], m[s, 0][None])
            for b, m in zip(scores_b, scores_m)
            for s in range(b.shape[0])
        ]
        divergence = float(np.mean(divs))
        assert divergence <= plan.divergence_cap, (
            f"mixed stream diverges {divergence:.3e} > declared cap "
            f"{plan.divergence_cap:.3e}"
        )

        result["mixedprec_bytes_saved_frac"] = round(1.0 - bytes_m / bytes_b, 3)
        result["mixedprec_divergence"] = divergence
        result["mixedprec_divergence_cap"] = plan.divergence_cap
        counts = plan.counts()
        result["mixedprec_plan"] = (
            f"{counts['bf16']}xbf16/{counts['int8']}xint8/"
            f"{counts['int4']}xint4"
        )
        log(
            f"mixedprec: bytes_saved_frac="
            f"{result['mixedprec_bytes_saved_frac']} "
            f"({bytes_m / 1e6:.1f} MB vs {bytes_b / 1e6:.1f} MB/sweep) "
            f"plan={result['mixedprec_plan']} "
            f"divergence={divergence:.3e} cap={plan.divergence_cap:.3e}"
        )
    except Exception:
        log("mixedprec bench failed:\n" + traceback.format_exc())


def bench_trace_overhead(
    result: dict, prompts, tok, budget_left, fw
) -> None:
    """Observability-PR satellite evidence: the span tracer must be
    effectively free, so it can stay compiled into every hot loop and be
    switched on in production without a perf conversation.

    ``trace_overhead_ratio``: full streaming sweep with tracing OFF vs
    the same sweep with the tracer ENABLED (ring recording every span),
    rotation-paired back-to-back like the hostcache/residency phases so
    disk and scheduler drift cancel. ~1.0 means tracing-on costs noise;
    a ratio sinking below ~0.85 means span recording has crept onto the
    hot path. The trace-OFF arm is the production default path (the
    per-emit cost there is one bool check), so the perf gate's advisory
    floor on this ratio also pins that the no-op path stays a no-op —
    tracing can never silently regress the hot path either way.
    """
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    tracer = obs_trace.TRACER
    was_enabled = tracer.enabled
    try:
        base = fw(None)
        sub = prompts[: min(4, len(prompts))]
        run_once(base, sub, tok)  # warm/compile outside both arms
        ratios = []
        for i in range(3):
            tracer.disable()
            _, w_off, _ = run_once(base, sub, tok)
            tracer.enable()
            try:
                _, w_on, _ = run_once(base, sub, tok)
            finally:
                tracer.disable()
                tracer.clear()  # a bench ring must not leak into a real run
            ratios.append(w_off / w_on)
            log(
                f"trace-overhead pair {i}: off={w_off:.2f}s on={w_on:.2f}s "
                f"ratio={ratios[-1]:.3f}"
            )
            if budget_left() < 0.7:
                log("  trace-overhead pair budget exhausted; stopping reps")
                break
        _ratio_stats(result, "trace_overhead_ratio", ratios)
        log(f"trace overhead: ratio={result['trace_overhead_ratio']}")
    except Exception:
        log("trace-overhead bench failed:\n" + traceback.format_exc())
    finally:
        if was_enabled:
            tracer.enable()
        else:
            tracer.disable()


def bench_recorder_overhead(
    result: dict, prompts, tok, budget_left, fw
) -> None:
    """Flight-recorder satellite evidence (docs/incidents.md): durability
    must be free on the serving hot path.

    ``recorder_overhead_ratio``: an identical small SERVE session —
    admit, prefill, decode, resolve — with the journal OFF vs the
    journal armed to a real directory with the incident recorder
    attached, rotation-paired back-to-back like the trace-overhead
    phase so disk and scheduler drift cancel. The journal's emit sites
    are failure paths only (never per token/shard/sweep), so a healthy
    serve with the recorder armed must cost noise (~1.0); a ratio
    sinking below ~0.85 means journaling crept onto the hot path. The
    journal-OFF arm is the production default (one bool per failure
    event), so the perf gate's advisory floor also pins that the no-op
    path stays a no-op.
    """
    import shutil as _shutil

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.obs import events as obs_events
    from flexible_llm_sharding_tpu.obs import incident as obs_incident
    from flexible_llm_sharding_tpu.serve import ServeEngine

    journal_dir = os.path.join(BENCH_DIR, "recorder_journal")

    def serve_once(base) -> float:
        engine = ServeEngine(
            base,
            ServeConfig(max_wave_requests=4, default_max_new_tokens=4),
            tokenizer=tok,
            start=False,
        )
        t0 = time.perf_counter()
        try:
            reqs = [
                engine.submit(p, s)
                for p, s in prompts[: min(4, len(prompts))]
            ]
            engine.start()
            for r in reqs:
                r.future.result(timeout=600)
        finally:
            engine.shutdown(drain=True)
        if engine.error is not None:
            raise RuntimeError(f"recorder bench engine error: {engine.error!r}")
        return time.perf_counter() - t0

    try:
        base = fw(None)
        serve_once(base)  # warm/compile outside both arms
        ratios = []
        for i in range(3):
            obs_events.reset_journal()
            w_off = serve_once(base)
            _shutil.rmtree(journal_dir, ignore_errors=True)
            obs_events.JOURNAL.configure(journal_dir)
            obs_events.JOURNAL.attach_recorder(
                obs_incident.IncidentRecorder(journal_dir, settle_s=0)
            )
            try:
                w_on = serve_once(base)
            finally:
                obs_events.reset_journal()  # a bench journal must not leak
            ratios.append(w_off / w_on)
            log(
                f"recorder-overhead pair {i}: off={w_off:.2f}s "
                f"on={w_on:.2f}s ratio={ratios[-1]:.3f}"
            )
            if budget_left() < 0.7:
                log("  recorder-overhead pair budget exhausted; stopping reps")
                break
        _ratio_stats(result, "recorder_overhead_ratio", ratios)
        log(f"recorder overhead: ratio={result['recorder_overhead_ratio']}")
    except Exception:
        log("recorder-overhead bench failed:\n" + traceback.format_exc())
    finally:
        obs_events.reset_journal()
        _shutil.rmtree(journal_dir, ignore_errors=True)


def bench_wal_overhead(
    result: dict, prompts, tok, budget_left, fw
) -> None:
    """Crash-safe serving satellite evidence (docs/recovery.md): the
    durable request WAL must be (near) free on the serving hot path.

    ``wal_overhead_ratio``: an identical small serve session — admit,
    prefill, decode, resolve — with the WAL off vs armed to a real
    directory under the default fsync policy (``admit``: admissions and
    terminals fsync; sweep-boundary progress records ride the kernel
    buffers), rotation-paired back-to-back like the trace/recorder
    phases so disk and scheduler drift cancel. WAL writes happen per
    request event and per sweep boundary — never per token or per shard
    — so a healthy serve with the WAL armed must cost noise (~1.0); a
    sinking ratio means journaling crept onto the per-shard path or the
    fsync policy silently broadened.
    """
    import shutil as _shutil

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.serve import ServeEngine

    wal_dir = os.path.join(BENCH_DIR, "wal_bench")

    def serve_once(base, wdir: str) -> float:
        engine = ServeEngine(
            base,
            ServeConfig(
                max_wave_requests=4,
                default_max_new_tokens=4,
                wal_dir=wdir,
            ),
            tokenizer=tok,
            start=False,
        )
        t0 = time.perf_counter()
        try:
            reqs = [
                engine.submit(p, s)
                for p, s in prompts[: min(4, len(prompts))]
            ]
            engine.start()
            for r in reqs:
                r.future.result(timeout=600)
        finally:
            engine.shutdown(drain=True)
            if engine._wal is not None:
                engine._wal.close()
        if engine.error is not None:
            raise RuntimeError(f"wal bench engine error: {engine.error!r}")
        return time.perf_counter() - t0

    try:
        base = fw(None)
        serve_once(base, "")  # warm/compile outside both arms
        ratios = []
        for i in range(3):
            w_off = serve_once(base, "")
            _shutil.rmtree(wal_dir, ignore_errors=True)
            w_on = serve_once(base, wal_dir)
            ratios.append(w_off / w_on)
            log(
                f"wal-overhead pair {i}: off={w_off:.2f}s "
                f"on={w_on:.2f}s ratio={ratios[-1]:.3f}"
            )
            if budget_left() < 0.7:
                log("  wal-overhead pair budget exhausted; stopping reps")
                break
        _ratio_stats(result, "wal_overhead_ratio", ratios)
        log(f"wal overhead: ratio={result['wal_overhead_ratio']}")
    except Exception:
        log("wal-overhead bench failed:\n" + traceback.format_exc())
    finally:
        _shutil.rmtree(wal_dir, ignore_errors=True)


def bench_fleet_stagger(result: dict) -> None:
    """Closed-loop sweep-stagger evidence (serve/autoscale.py,
    docs/autoscale.md): the controller must pull an in-phase fleet to
    the i/N offsets and RE-converge after a membership perturbation.

    ``fleet_stagger_convergence``: 1 - final stagger error of a
    deterministic two-replica closed loop — synthetic sweep clocks feed
    the REAL controller through its injected ``now``/``observe``
    surface, and its boundary holds feed back into the synthetic
    schedules. Both replicas start dead in phase (error 1.0, the
    worst case), must converge below tolerance, then a simulated
    recycle (membership change + a 0.25-sweep phase jump) must
    re-converge. Structural and timing-free (no wall clocks anywhere):
    a healthy controller lands ~1.0; the hold math disengaging leaves
    the initial error standing, which no runner noise can fake. The
    phase refuses to record a value unless holds were actually applied
    in BOTH rounds — convergence without actuation would mean the sim
    went in-phase by accident, not that the controller works.
    """
    from flexible_llm_sharding_tpu.config import AutoscaleConfig
    from flexible_llm_sharding_tpu.serve.autoscale import StaggerController

    ctl = StaggerController(
        AutoscaleConfig(enabled=True, stagger_tolerance=0.05)
    )
    wall = 1.0
    nxt = {0: 0.0, 1: 0.0}  # next shard-0 boundary arrival
    start = {0: 0.0, 1: 0.0}  # current sweep start (after any hold)
    t = 0.0
    err = 1.0
    holds_by_round = [0, 0]
    for step in range(800):
        t = round(t + 0.1, 6)
        if step == 400:
            # Mid-sim recycle: the fleet drops the pending holds and the
            # "new" replica comes back wherever chaos put it.
            ctl.note_membership_change()
            nxt[1] = round(nxt[1] + 0.25 * wall, 6)
            start[1] = nxt[1] - wall
        for idx in (0, 1):
            while t >= nxt[idx]:
                hold = ctl.on_boundary(idx, nxt[idx])
                if hold > 0.0:
                    holds_by_round[0 if step < 400 else 1] += 1
                start[idx] = nxt[idx] + hold
                nxt[idx] = round(start[idx] + wall, 6)
        phases = {
            i: min(max((t - start[i]) / wall, 0.0), 0.999) for i in (0, 1)
        }
        err = ctl.observe(phases)
    stats = ctl.stats()
    if holds_by_round[0] < 1 or holds_by_round[1] < 1:
        log(
            f"fleet stagger: controller never actuated "
            f"(holds_by_round={holds_by_round}, stats={stats}) — "
            f"refusing to record"
        )
        return
    result["fleet_stagger_convergence"] = round(1.0 - err, 3)
    log(
        f"fleet stagger: convergence="
        f"{result['fleet_stagger_convergence']} (final error "
        f"{stats['stagger_error']}, holds={stats['holds_applied']}, "
        f"restaggers={stats['restaggers']})"
    )


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _overlap_efficiency(stats: dict) -> float | None:
    """1 - source_wait/produce from an executor's stats — the fraction of
    weight-produce time hidden under compute (None without the timers)."""
    prod = stats.get("produce_wall_s")
    if not prod:
        return None
    return max(0.0, min(1.0, (prod - stats["source_wait_s"]) / prod))


def _ratio_stats(result: dict, key: str, ratios) -> None:
    """Median + dispersion for a measured ratio (the rig's
    run-to-run noise can exceed ±25%, so a bare ratio is uninterpretable).
    Writes ``key`` (median), ``key_spread`` ([min, median, max]) and — when
    the spread straddles 1.0 — ``key_inconclusive``: such a ratio cannot
    establish a win or a loss on its own and must say so in the artifact."""
    lo, med, hi = (
        float(np.min(ratios)),
        float(np.median(ratios)),
        float(np.max(ratios)),
    )
    result[key] = round(med, 3)
    result[key + "_spread"] = [round(lo, 3), round(med, 3), round(hi, 3)]
    result[key + "_n"] = len(ratios)
    # Always written (never popped): an absent flag next to a stale True
    # from an earlier call on the same dict would pair a
    # fresh conclusive median with a stale inconclusive verdict. A single
    # rep (budget-truncated pair loop) is ALWAYS inconclusive — one noisy
    # ratio cannot establish a win or a loss.
    result[key + "_inconclusive"] = bool(
        len(ratios) < 2 or lo < 1.0 < hi
    )


def _ref_layer_fn():
    """Single-layer, batch-of-one jitted decoder apply for the
    reference-schedule emulation. The reference executes ONE HF layer module
    at a time (no stacked scan); jitting the single layer is the honest
    analog of its precompiled CUDA kernels — the schedule differences under
    measurement (per-tensor sync uploads, serialized load-then-compute,
    per-prompt loop) are preserved, the per-op math is compiled in both."""
    if getattr(_ref_layer_fn, "fn", None) is None:
        import functools

        import jax

        from flexible_llm_sharding_tpu.models import llama

        @functools.partial(jax.jit, static_argnums=(0,))
        def f(cfg, lp, ph, sh, plen):
            def one(p_, s_, n_):
                return llama.prefix_suffix_layer(lp, cfg, p_, s_, n_)

            return jax.vmap(one)(ph, sh, plen)

        _ref_layer_fn.fn = f
    return _ref_layer_fn.fn


def _reference_schedule_run(jax, ex, toks):
    """One full scoring pass under the REFERENCE's own execution schedule,
    emulated faithfully (``/root/reference/utils.py``):

    - per-tensor SYNCHRONOUS uploads — one blocking ``device_put`` per
      parameter tensor (``set_module_tensor_to_device`` per param,
      ``utils.py:128-130``), no prefetch thread, each shard's load fully
      serialized before its compute (``utils.py:228-233``);
    - no stacked-layer scan — a single-layer jitted program applied
      layer-by-layer (the reference runs one HF module at a time);
    - per-PROMPT python loop, batch of one (``utils.py:236-239``) — no
      cross-prompt blocking;
    - activations round-trip through host numpy between shards (the
      ``storage_location='cpu'`` semantics, ``utils.py:164-168,191-195``).

    Same tokenization, same layer math, same scores as the overlapped
    executor — ONLY the schedule differs, so the wall ratio isolates the
    schedule design. Returns (scores, wall_s, load_s)."""
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.runtime.executor import (
        _HostShardLoader,
        _embed_block,
        _head_block,
        _norm_block,
    )

    cfg, dtype, device = ex.model_cfg, ex.dtype, ex.device
    loader = _HostShardLoader(
        ex.cfg.model_path,
        ex.layer_names,
        ex._np_dtype,
        tied_embeddings=cfg.tie_word_embeddings,
        readahead="off",
    )
    layer_fn = _ref_layer_fn()
    n = len(ex.layer_names)
    acts: dict[int, tuple] = {}
    scores: list = [None] * len(toks)
    t0 = time.perf_counter()
    load_s = 0.0
    for li, name in enumerate(ex.layer_names):
        tl = time.perf_counter()
        params = loader._cast(loader._load_one(name))
        leaves, tdef = jax.tree.flatten(params)
        up = []
        for leaf in leaves:  # one blocking upload per tensor
            a = jax.device_put(jnp.asarray(leaf), device)
            jax.block_until_ready(a)
            up.append(a)
        pdev = jax.tree.unflatten(tdef, up)
        load_s += time.perf_counter() - tl
        for p, t in enumerate(toks):
            if li == 0:
                ph, sh = _embed_block(
                    cfg,
                    dtype,
                    pdev,
                    jnp.asarray(t.prefix_ids)[None],
                    jnp.asarray(t.suffix_ids)[None],
                )
            else:
                ph_np, sh_np = acts[p]
                sh = jax.device_put(jnp.asarray(sh_np), device)
                ph = (
                    jax.device_put(jnp.asarray(ph_np), device)
                    if ph_np is not None
                    else None
                )
                if name.startswith("model.layers."):
                    ph, sh = layer_fn(
                        cfg, pdev, ph, sh,
                        jnp.asarray([t.prefix_len], jnp.int32),
                    )
                elif name == "model.norm":
                    sh = _norm_block(
                        cfg, pdev, sh, jnp.asarray(t.suffix_eos)[None]
                    )
                    ph = None
                else:  # lm_head
                    sc = _head_block(cfg, pdev, sh)
                    scores[p] = np.asarray(sc)[0, : t.num_suffixes, None, :]
                    continue
            # Host round-trip per prompt per shard (np.asarray blocks — the
            # reference's .cpu() is synchronous too). The prefix is only
            # needed through the last decoder (executor: with_prefix rule).
            acts[p] = (
                np.asarray(ph) if (ph is not None and li < n - 3) else None,
                np.asarray(sh),
            )
    wall = time.perf_counter() - t0
    loader.close()
    return scores, wall, load_s


def bench_reference_schedule(
    jax, cfg_default, prompts, tok, result: dict, budget_left
) -> None:
    """``vs_reference_schedule``: the overlapped executor vs a faithful
    emulation of the reference's schedule on the same workload (VERDICT r3
    weak #1: ``vs_baseline`` compares the SAME executor at prefetch 0, which
    already has stacked uploads, blocked prompts and jitted scans — this is
    the measured ratio against the schedule the reference actually runs).
    Paired back-to-back reps with median-of-ratios and dispersion."""
    from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor

    sub = prompts[: min(4, len(prompts))]
    ex = StreamingExecutor(cfg_default, tokenizer=tok)
    toks = ex._tokenize(sub)
    # Warm/compile both sides (the emulation's per-layer jit; the executor's
    # block programs may see a new batch shape for the subset).
    _reference_schedule_run(jax, ex, toks)
    ovl_scores, _, _ex = run_once(cfg_default, sub, tok)

    ratios, load_ss, maxerr = [], [], 0.0
    for i in range(3):
        ref_scores, w_ref, load_s = _reference_schedule_run(jax, ex, toks)
        _, w_ovl, _ = run_once(cfg_default, sub, tok)
        ratios.append(w_ref / w_ovl)
        load_ss.append(load_s)
        for a, b in zip(ref_scores, ovl_scores):
            maxerr = max(
                maxerr,
                float(
                    np.abs(
                        np.asarray(a, np.float32) - np.asarray(b, np.float32)
                    ).max()
                ),
            )
        log(
            f"ref-schedule pair {i}: ref={w_ref:.2f}s overlapped={w_ovl:.2f}s "
            f"ratio={ratios[-1]:.3f} (ref load={load_s:.2f}s)"
        )
        _ratio_stats(result, "vs_reference_schedule", ratios)
        result["ref_schedule_load_s"] = round(float(np.median(load_ss)), 3)
        result["ref_schedule_score_maxerr"] = float(f"{maxerr:.3e}")
        if budget_left() < 0.45:
            log("  ref-schedule budget exhausted; stopping reps")
            break


def bench_resident_mfu(
    jax, result: dict, budget_left, cfg=None, B=4, T=2048, iters=8
) -> None:
    """Compute-bound MFU with HBM-resident weights: the end-to-end mfu of a
    streamed pass is bound by the host->HBM link and says nothing about
    kernel/compiler quality; this phase takes the link out.

    A 4-layer 4096-wide llama (~1.9 GB bf16 — fits one v5e's 16 GB with
    room for activations) runs the monolithic causal forward
    (models/llama.py forward_full — the same layer math the streamed
    executor scans) over a [4, 2048]-token batch with parameters CREATED ON
    DEVICE and kept resident: zero weight-stream bytes inside the measured
    window, emulating the resident/fused decode regime (runtime/decode.py)
    where weights upload once and then serve many steps. ITERS passes are
    dispatched back-to-back with one scalar read at the end, so dispatch
    latency amortises (the XLA queue keeps the chip busy).

    mfu_resident = analytic model-FLOPs/token x tokens/sec over the chip's
    peak bf16 FLOP/s. This substantiates the compute path's quality; the
    streaming path's end-to-end mfu stays link-bound by design and is
    reported separately against host_to_hbm_gbps."""
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama
    from flexible_llm_sharding_tpu.utils.metrics import (
        chip_peak_flops,
        model_flops_per_token,
    )

    dev = jax.devices()[0]
    peak = chip_peak_flops(dev)
    if peak is None:
        log("resident MFU: unknown chip peak FLOP/s; skipping")
        return
    if cfg is None:  # the production shape; tests pass a tiny one
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=11008,
            num_hidden_layers=4,
            num_attention_heads=32,
            num_key_value_heads=32,
            max_position_embeddings=4096,
        )
    params = llama.init_params(jax.random.PRNGKey(7), cfg, dtype=jnp.bfloat16)
    ids = jax.device_put(
        np.asarray(
            np.random.default_rng(7).integers(3, cfg.vocab_size, (B, T)),
            np.int32,
        ),
        dev,
    )

    @jax.jit
    def score_pass(p, i):
        # Scalar read-back: the [B, T, V] logits stay on device (a ~1 GB
        # device_get per pass would swamp the timing).
        return llama.forward_full(p, cfg, i, dtype=jnp.bfloat16).sum()

    jax.block_until_ready(params)
    jax.device_get(score_pass(params, ids))  # compile + first pass
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = score_pass(params, ids)
    jax.device_get(out)  # in-order stream: waits for all queued passes
    dt = (time.perf_counter() - t0) / iters
    fpt = model_flops_per_token(cfg, context_len=T // 2)  # mean causal ctx
    tps = B * T / dt
    result["mfu_resident"] = round(fpt * tps / peak, 4)
    result["resident_tokens_per_sec"] = round(tps, 1)
    result["resident_pass_s"] = round(dt, 4)
    result["resident_model_flops_per_token"] = round(fpt)
    log(
        f"resident MFU: {result['mfu_resident']} ({tps:.0f} tok/s, "
        f"{dt*1e3:.1f} ms/pass, fpt={fpt/1e9:.2f} GF/token)"
    )


def _set_throughput(result: dict, total_tokens: int, wall: float, dev) -> None:
    """Headline throughput + derived MFU/TFLOPs from the best overlapped
    wall — ONE derivation shared by the first-measure and post-pairs sites."""
    tps = total_tokens / wall
    result["value"] = round(tps, 2)
    result["tokens_per_sec"] = round(tps, 2)
    result["tokens_per_sec_per_chip"] = round(tps, 2)  # single-chip bench
    fpt = result.get("model_flops_per_token")
    if fpt:
        from flexible_llm_sharding_tpu.utils.metrics import chip_peak_flops

        result["model_tflops_per_sec"] = round(fpt * tps / 1e12, 4)
        peak_fl = chip_peak_flops(dev)
        if peak_fl:
            result["mfu"] = round(fpt * tps / peak_fl, 6)


def _make_replay_draft(tok, prompt, chain):
    """Replay draft source: propose the plain run's own greedy ``chain``
    verbatim, making acceptance exactly 1.0 — the verification
    mechanism's upper bound, isolated from draft quality. ``base_len``
    mirrors the PromptTokenizer context layout (prefix ids incl. BOS +
    suffix ids minus the shared leading BOS). ONE helper shared by
    bench_spec (offline mechanism wall ratio) and bench_spec_serve
    (serving tokens-per-sweep) so the done-offset arithmetic cannot
    drift between the two phases."""
    base_len = (
        len(tok(prompt[0])["input_ids"])
        + len(tok(prompt[1][0])["input_ids"])
        - 1
    )

    def replay_draft(context_ids, k, ngram=2, corpus=None):
        done = len(context_ids) - base_len  # tokens generated so far
        d = list(chain[done : done + k])
        while len(d) < k:
            d.append(d[-1] if d else chain[-1])
        return np.asarray(d, np.int64)

    return replay_draft


def bench_spec(cfg_obj, tok, result: dict, budget_left, n_tok: int = 8, k: int = 8) -> None:
    """Speculative streamed decode vs plain streamed decode.
    decode_resident='off' emulates the regime the mode exists for — a model
    too big for HBM, where EVERY decode step re-streams the full weights —
    so the measured ratio is the weight-stream amortisation from verifying
    k drafts per pass.

    Two draft sources are measured, because draft QUALITY is a property of
    the model+workload, not the mechanism:
    - spec_decode_speedup / spec_acceptance: prompt-lookup drafting
      (runtime/decode.py propose_draft) on a repetition-heavy workload.
      The synthetic random-weight bench model need not follow its prompt's
      n-grams, so acceptance here can be near zero — at which point the
      true ratio is ~1 (same number of weight streams, K+1-wide verify
      steps) and any larger reading is noise.
    - spec_mechanism_speedup: a replay draft source (the plain run's own
      greedy picks, injectable via DecodeGenerator(draft_fn=...)) forces
      acceptance 1.0, isolating the verification mechanism's amortisation
      upper bound from draft quality.

    Drift defences: the measurement order within each triple rotates with
    the pair index, so every generator occupies every slot across the reps
    and a monotone link-speed trend can't systematically inflate one side;
    acceptance aggregates over ALL pairs; per-pair raw seconds are
    recorded under spec_pairs."""
    import dataclasses

    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

    rng = np.random.default_rng(1)
    words = [f"w{i}" for i in range(40)]
    phrase = " ".join(rng.choice(words, size=12))
    prompts = [
        (f"{phrase} {phrase} {phrase}", (f" {phrase}", f" {phrase}"))
        for _ in range(2)
    ]
    base = dataclasses.replace(
        cfg_obj,
        num_gen_token=n_tok,
        decode_resident="off",
        decode_fused="off",
    )
    plain = DecodeGenerator(base, tokenizer=tok)
    plain_scores, _ = plain(prompts)  # warm/compile
    spec_cfg = dataclasses.replace(base, speculative_k=k)
    spec = DecodeGenerator(spec_cfg, tokenizer=tok)
    spec(prompts)  # warm/compile

    # Replay draft source: every workload sequence is identical by
    # construction, so the plain run's greedy chain (argmax over its score
    # history for prompt 0 / suffix 0) IS the continuation every suffix
    # will produce; drafting it verbatim makes acceptance exactly 1.0.
    # Guard the premise : if the workload ever diversifies,
    # acceptance silently drops and the mechanism number understates.
    assert all(p == prompts[0] for p in prompts) and all(
        s == prompts[0][1][0] for s in prompts[0][1]
    ), "replay draft source requires an all-identical spec workload"
    chain = [int(np.argmax(plain_scores[0][0, t])) for t in range(n_tok)]
    replay_draft = _make_replay_draft(tok, prompts[0], chain)

    mech = DecodeGenerator(spec_cfg, tokenizer=tok, draft_fn=replay_draft)
    mech(prompts)  # warm/compile

    def timed(gen):
        t0 = time.perf_counter()
        gen(prompts)
        return time.perf_counter() - t0

    ratios, mech_ratios, pairs = [], [], []
    acc_tot = drafted_tot = 0.0
    gens = [("plain", plain), ("spec", spec), ("mech", mech)]
    for i in range(4):
        order = gens[i % 3 :] + gens[: i % 3]  # rotate the slot assignment
        t = {name: timed(gen) for name, gen in order}
        ratios.append(t["plain"] / t["spec"])
        mech_ratios.append(t["plain"] / t["mech"])
        st = spec.stats
        acc_tot += st.get("spec_accepted", 0.0)
        drafted_tot += st.get("spec_drafted", 0.0)
        mech_st = mech.stats
        pairs.append(
            {
                "plain_s": round(t["plain"], 3),
                "spec_s": round(t["spec"], 3),
                "mech_s": round(t["mech"], 3),
                "accepted": st.get("spec_accepted"),
                "drafted": st.get("spec_drafted"),
                "mech_accepted": mech_st.get("spec_accepted"),
            }
        )
        log(
            f"spec pair {i}: plain={t['plain']:.2f}s spec={t['spec']:.2f}s "
            f"mech={t['mech']:.2f}s ratio={ratios[-1]:.3f} "
            f"mech_ratio={mech_ratios[-1]:.3f} "
            f"accepted={st.get('spec_accepted')}/{st.get('spec_drafted')} "
            f"mech_accepted={mech_st.get('spec_accepted')}/"
            f"{mech_st.get('spec_drafted')}"
        )
        _ratio_stats(result, "spec_decode_speedup", ratios)
        _ratio_stats(result, "spec_mechanism_speedup", mech_ratios)
        result["spec_acceptance"] = round(acc_tot / max(drafted_tot, 1.0), 3)
        result["spec_pairs"] = pairs
        if budget_left() < 0.06:
            log("  spec pair budget exhausted; stopping reps")
            break


def bench_spec_serve(
    cfg_obj, tok, result: dict, budget_left, n_tok: int = 8, k: int = 7
) -> None:
    """Serve-level speculative headline: tokens per weight sweep.

    Runs the SERVING engine (continuous batching, ServeConfig.
    speculative_k, serve/engine.py) spec-off then spec-on on an identical
    two-request wave, with a replay draft source (the spec-off run's own
    greedy chain, monkey-installed over propose_draft) forcing acceptance
    1.0 — the mechanism's upper bound isolated from draft quality,
    exactly the spec_mechanism_speedup idea lifted to the serving path.
    Token-identity between the two runs is asserted first, so the
    numbers can never come from a diverged stream. Records:

    - ``spec_serve_tokens_per_sweep``: tokens emitted / weight sweeps in
      the spec-on run — the serving headline (plain serving is exactly 1
      decode token per suffix per sweep plus the prefill sweep).
    - ``spec_serve_sweep_ratio``: plain sweeps / spec sweeps on the SAME
      workload — structural and timing-free (the pinned_fraction idea):
      a lost mechanism collapses it to ~1.0, which no runner noise can
      hide.
    - ``spec_serve_acceptance``: accepted/drafted across the spec run.
    """
    import dataclasses

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.runtime import decode as decode_mod
    from flexible_llm_sharding_tpu.serve import ServeEngine

    rng = np.random.default_rng(7)
    words = [f"w{i}" for i in range(40)]
    phrase = " ".join(rng.choice(words, size=12))
    prompt = (f"{phrase} {phrase} {phrase}", (f" {phrase}",))
    base = dataclasses.replace(cfg_obj, num_gen_token=n_tok)

    def run(spec_k):
        engine = ServeEngine(
            base,
            ServeConfig(
                max_wave_requests=2,
                default_max_new_tokens=n_tok,
                speculative_k=spec_k,
            ),
            tokenizer=tok,
            start=False,  # both requests admit at ONE boundary
        )
        try:
            reqs = [engine.submit(*prompt) for _ in range(2)]
            engine.start()
            out = [r.future.result(timeout=600) for r in reqs]
        finally:
            engine.shutdown(drain=True)
        if engine.error is not None:
            raise RuntimeError(f"serve bench engine error: {engine.error!r}")
        return out, engine.stats()

    plain, plain_stats = run(0)
    chain = [int(t) for t in plain[0].tokens[0]]
    replay_draft = _make_replay_draft(tok, prompt, chain)

    orig = decode_mod.propose_draft
    decode_mod.propose_draft = replay_draft
    try:
        spec, spec_stats = run(k)
    finally:
        decode_mod.propose_draft = orig

    for p, s in zip(plain, spec):
        if not (p.tokens == s.tokens).all():
            raise RuntimeError(
                "spec-on serve run diverged from spec-off (greedy-exact "
                "verification broken) — refusing to record its numbers"
            )
    tokens = spec_stats["tokens_emitted"]
    result["spec_serve_tokens_per_sweep"] = round(
        tokens / spec_stats["sweeps"], 3
    )
    result["spec_serve_sweep_ratio"] = round(
        plain_stats["sweeps"] / spec_stats["sweeps"], 3
    )
    result["spec_serve_acceptance"] = spec_stats.get("spec", {}).get(
        "acceptance_rate", 0.0
    )
    log(
        f"spec serve: tokens_per_sweep={result['spec_serve_tokens_per_sweep']} "
        f"sweep_ratio={result['spec_serve_sweep_ratio']} "
        f"(plain {plain_stats['sweeps']} sweeps -> spec "
        f"{spec_stats['sweeps']}) acceptance="
        f"{result['spec_serve_acceptance']}"
    )


def bench_spec_adaptive(
    cfg_obj, tok, result: dict, budget_left, n_tok: int = 12,
    start_k: int = 2, k_max: int = 7,
) -> None:
    """Resident draft model + adaptive-k headline: the acceptance-driven
    k trajectory, at zero extra per-sweep stream bytes.

    Serves the same two-request wave plain (k=0) then adaptive with the
    TARGET checkpoint doubling as the resident draft model — every draft
    agrees with verification, so acceptance is deterministically 1.0 and
    the windowed controller must climb k from ``start_k`` toward
    ``k_max`` pass over pass (the mechanism's upper bound isolated from
    draft quality, the replay-draft idea realised through the real
    runtime/draft.py path: pinned residency tier, real forwards). Both
    runs force float32: at bfloat16 the draft's full-context recompute
    and the target's KV-cached verify pass diverge in argmax often
    enough (~0.6 acceptance) to turn the deterministic trajectory into a
    rounding artifact. Token-identity AND the structural
    zero-extra-stream claim (adaptive
    per-sweep streamed bytes == plain per-sweep streamed bytes, from the
    executors' own counters) are asserted before recording. Records:

    - ``spec_adaptive_tokens_per_sweep``: tokens emitted / weight sweeps
      in the adaptive run — the serving headline with the controller and
      draft model live end to end.
    - ``spec_adaptive_sweep_ratio``: plain sweeps / adaptive sweeps on
      the SAME workload (structural and timing-free).
    - ``spec_adaptive_k_final``: the largest per-class k the controller
      reached — the acceptance-driven trajectory (start_k means the
      control loop never moved; a lost observe/raise path cannot hide).
    - ``spec_adaptive_acceptance``: accepted/drafted across the run.
    """
    import dataclasses

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.runtime.executor import stream_stats
    from flexible_llm_sharding_tpu.serve import ServeEngine

    rng = np.random.default_rng(11)
    words = [f"w{i}" for i in range(40)]
    phrase = " ".join(rng.choice(words, size=12))
    prompt = (f"{phrase} {phrase} {phrase}", (f" {phrase}",))
    base = dataclasses.replace(cfg_obj, num_gen_token=n_tok,
                               dtype="float32")

    def run(serve_kw):
        engine = ServeEngine(
            base,
            ServeConfig(
                max_wave_requests=2,
                default_max_new_tokens=n_tok,
                **serve_kw,
            ),
            tokenizer=tok,
            start=False,  # both requests admit at ONE boundary
        )
        # Measured AFTER construction: the draft pin loads once there,
        # outside the per-sweep window the claim is about.
        bytes0 = stream_stats()["streamed_bytes"]
        try:
            reqs = [engine.submit(*prompt) for _ in range(2)]
            engine.start()
            out = [r.future.result(timeout=600) for r in reqs]
        finally:
            engine.shutdown(drain=True)
        if engine.error is not None:
            raise RuntimeError(
                f"adaptive bench engine error: {engine.error!r}"
            )
        return out, engine.stats(), stream_stats()["streamed_bytes"] - bytes0

    plain, plain_stats, plain_bytes = run({})
    spec, spec_stats, spec_bytes = run(dict(
        speculative_k=start_k,
        spec_adaptive=True,
        spec_k_max=k_max,
        spec_window=1,
        draft_model_path=base.model_path,
    ))

    for p, s in zip(plain, spec):
        if not (p.tokens == s.tokens).all():
            raise RuntimeError(
                "adaptive serve run diverged from plain (greedy-exact "
                "verification broken) — refusing to record its numbers"
            )
    per_sweep, rem = divmod(plain_bytes, plain_stats["sweeps"])
    if rem != 0 or spec_bytes != per_sweep * spec_stats["sweeps"]:
        raise RuntimeError(
            "adaptive run streamed extra per-sweep bytes (draft model "
            f"not free: plain {plain_bytes}B/{plain_stats['sweeps']} "
            f"sweeps vs adaptive {spec_bytes}B/{spec_stats['sweeps']}) "
            "— refusing to record its numbers"
        )
    result["spec_adaptive_tokens_per_sweep"] = round(
        spec_stats["tokens_emitted"] / spec_stats["sweeps"], 3
    )
    result["spec_adaptive_sweep_ratio"] = round(
        plain_stats["sweeps"] / spec_stats["sweeps"], 3
    )
    result["spec_adaptive_k_final"] = max(
        spec_stats["spec_ctrl"]["k_by_class"].values()
    )
    result["spec_adaptive_acceptance"] = spec_stats.get("spec", {}).get(
        "acceptance_rate", 0.0
    )
    log(
        f"spec adaptive: tokens_per_sweep="
        f"{result['spec_adaptive_tokens_per_sweep']} "
        f"sweep_ratio={result['spec_adaptive_sweep_ratio']} "
        f"(plain {plain_stats['sweeps']} sweeps -> adaptive "
        f"{spec_stats['sweeps']}) k {start_k}->"
        f"{result['spec_adaptive_k_final']} acceptance="
        f"{result['spec_adaptive_acceptance']}"
    )


def bench_kv_reuse(cfg_obj, tok, result: dict, budget_left,
                   n_tok: int = 8) -> None:
    """Paged prefix-KV pool headline: fraction of total prefix prefill
    work served from pooled pages across two sequential same-prefix
    waves (runtime/kvpool.py, docs/kvpool.md).

    Serves the SAME prefix twice with max_active_requests=1, forcing
    two waves: wave 1 prefills and contributes its pages, wave 2 must
    assemble them (zero prefix prefill recompute). Token-identity
    against a pool-off run of the identical workload is asserted FIRST,
    so the number can never come from a diverged stream. Records:

    - ``kv_prefix_reuse_frac``: prefix_reuse_tokens /
      (prefix_reuse_tokens + prefix_prefill_tokens) — structural and
      timing-free (token counters, not walls). Two same-prefix waves
      put the healthy value at exactly 0.5; the pool disengaging
      collapses it to 0.0, which no runner noise can fake.
    """
    import dataclasses

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.runtime import kvpool
    from flexible_llm_sharding_tpu.serve import ServeEngine

    rng = np.random.default_rng(11)
    words = [f"w{i}" for i in range(40)]
    phrase = " ".join(rng.choice(words, size=24))
    suffixes = (" alpha beta", " gamma delta")
    base = dataclasses.replace(cfg_obj, num_gen_token=n_tok)

    def run(pool_on):
        kvpool.reset_process_pools()  # no pages leak in from other phases
        cfg = base if pool_on else dataclasses.replace(base, kv_pool_gb=0.0)
        engine = ServeEngine(
            cfg,
            ServeConfig(
                max_wave_requests=1,
                max_active_requests=1,  # wave 2 starts after wave 1 retires
                default_max_new_tokens=n_tok,
            ),
            tokenizer=tok,
        )
        try:
            outs = [
                engine.submit(phrase, (sfx,)).future.result(timeout=600)
                for sfx in suffixes
            ]
        finally:
            engine.shutdown(drain=True)
        if engine.error is not None:
            raise RuntimeError(f"kv reuse bench engine error: {engine.error!r}")
        reuse = engine.metrics.counter("prefix_reuse_tokens")
        prefill = engine.metrics.counter("prefix_prefill_tokens")
        kvpool.reset_process_pools()
        return outs, reuse, prefill

    off, _, _ = run(False)
    on, reuse, prefill = run(True)
    for p, q in zip(off, on):
        if not (p.tokens == q.tokens).all():
            raise RuntimeError(
                "pool-on serve run diverged from pool-off (paged prefix "
                "reuse broken) — refusing to record its numbers"
            )
    if reuse <= 0:
        raise RuntimeError(
            "kv reuse bench: the second same-prefix wave reused no pooled "
            "prefix tokens"
        )
    result["kv_prefix_reuse_frac"] = round(reuse / (reuse + prefill), 3)
    log(
        f"kv reuse: frac={result['kv_prefix_reuse_frac']} "
        f"(prefill {prefill} tokens, reuse {reuse} tokens)"
    )


def bench_adapters(cfg_obj, tok, result: dict, budget_left,
                   n_tok: int = 8) -> None:
    """Multi-tenant LoRA delta streaming headlines (adapters/,
    docs/adapters.md).

    Serves the SAME three-request workload (two LoRA tenants + one base
    request) twice — adapters off (all-base) and adapters on — in one
    wave each, so both runs pay exactly one base-weight sweep per pass.
    The base tenant's tokens under adapters-on must match the all-base
    run bit-for-bit BEFORE anything is recorded (the zero-adapter rows
    ride group 0's zero delta), and the adapter store must report
    nonzero applied rows (parity alone would also pass if the deltas
    silently disengaged). Records:

    - ``adapter_overhead_ratio``: base-only serve wall / adapters-on
      serve wall on the identical workload, warm pass of each (the
      first pass of each run absorbs its jit compiles). The healthy
      value is ~parity: deltas ride the existing sweep's layer entries,
      they never add a sweep.
    - ``adapter_delta_bytes_frac``: adapter delta bytes moved across
      the host->device link / base weight bytes streamed in the same
      run, read from the store's and the stream's own byte counters —
      structural and timing-free. This is the paper-scale claim: a
      tenant costs rank-sized factors, not a base-model restream.
      Healthy value well under 0.05.
    """
    import dataclasses
    import tempfile

    from flexible_llm_sharding_tpu.adapters import loader as adapter_loader
    from flexible_llm_sharding_tpu.adapters.registry import save_adapter
    from flexible_llm_sharding_tpu.config import AdapterConfig, ServeConfig
    from flexible_llm_sharding_tpu.runtime.executor import (
        process_streamed_bytes,
    )
    from flexible_llm_sharding_tpu.serve import ServeEngine

    with open(os.path.join(cfg_obj.model_path, "config.json")) as f:
        mc = json.load(f)
    hidden = int(mc["hidden_size"])
    n_layers = int(mc["num_hidden_layers"])

    root = tempfile.mkdtemp(prefix="adapters_", dir=BENCH_DIR)
    rng = np.random.default_rng(17)
    for name in ("tenant-a", "tenant-b"):
        save_adapter(
            root,
            name,
            {
                f"model.layers.{i}": (
                    (0.02 * rng.standard_normal((hidden, 4))).astype(
                        np.float32
                    ),
                    (0.02 * rng.standard_normal((4, hidden))).astype(
                        np.float32
                    ),
                )
                for i in range(n_layers)
            },
        )

    words = [f"w{i}" for i in range(40)]
    prompts = [
        (" ".join(rng.choice(words, size=16)), (" alpha", " beta"))
        for _ in range(3)
    ]
    tenants = ("tenant-a", "tenant-b", None)
    base = dataclasses.replace(cfg_obj, num_gen_token=n_tok)

    def run(adapters_on):
        adapter_loader.reset_process_store()
        cfg = (
            dataclasses.replace(
                base, adapters=AdapterConfig(dir=root, max_gb=1.0)
            )
            if adapters_on
            else base
        )
        # The stream counter is process-cumulative (earlier phases and
        # reps included), so the fraction's denominator must be this
        # run's own delta.
        streamed0 = process_streamed_bytes()
        engine = ServeEngine(
            cfg, ServeConfig(default_max_new_tokens=n_tok), tokenizer=tok
        )
        try:
            outs, wall = None, None
            for _ in range(2):  # pass 1 compiles; pass 2 is the timed one
                t0 = time.perf_counter()
                futs = [
                    engine.submit(
                        pfx,
                        sfx,
                        adapter_id=aid if adapters_on else None,
                    ).future
                    for (pfx, sfx), aid in zip(prompts, tenants)
                ]
                outs = [f.result(timeout=600) for f in futs]
                wall = time.perf_counter() - t0
            streamed = process_streamed_bytes() - streamed0
        finally:
            engine.shutdown(drain=True)
        if engine.error is not None:
            raise RuntimeError(f"adapter bench engine error: {engine.error!r}")
        stats = (
            dict(adapter_loader.process_store().stats())
            if adapters_on
            else {}
        )
        adapter_loader.reset_process_store()
        return outs, wall, streamed, stats

    off, off_wall, _, _ = run(False)
    on, on_wall, streamed, stats = run(True)
    if not (off[2].tokens == on[2].tokens).all():
        raise RuntimeError(
            "base tenant diverged between adapters-off and adapters-on "
            "runs (zero-adapter path no longer byte-identical) — refusing "
            "to record its numbers"
        )
    if not stats.get("applied_rows"):
        raise RuntimeError(
            "adapter bench: the store applied no delta rows — the LoRA "
            "path silently disengaged"
        )
    frac = stats["delta_bytes"] / max(1, streamed)
    if frac >= 0.05:
        # Structural ceiling, asserted rather than floor-gated: the
        # healthy value (~1e-4) rounds any recorded-fraction floor to
        # zero, so the claim is pinned here, where measure() runs it.
        raise RuntimeError(
            f"adapter bench: delta bytes are {frac:.3f} of the streamed "
            "base bytes (>= 0.05) — tenants are no longer rank-sized"
        )
    result["adapter_overhead_ratio"] = round(off_wall / on_wall, 3)
    result["adapter_delta_bytes_frac"] = round(frac, 4)
    log(
        f"adapters: overhead_ratio={result['adapter_overhead_ratio']} "
        f"delta_bytes_frac={result['adapter_delta_bytes_frac']} "
        f"(delta {stats['delta_bytes']} B vs streamed {streamed} B, "
        f"applied_rows={stats['applied_rows']})"
    )


def run_bench(result: dict) -> None:
    t_bench0 = time.perf_counter()
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "2400"))

    def budget_left() -> float:
        """Fraction of the watchdog deadline still unspent — phase loops
        stop repeating when the later phases (pallas, decode) would starve.
        A non-positive deadline means 'no watchdog': never stop early."""
        if deadline_s <= 0:
            return 1.0
        return 1.0 - (time.perf_counter() - t_bench0) / deadline_s

    jax, devs = _init_jax()
    log(f"devices: {devs}")
    on_tpu = devs[0].platform != "cpu"
    result["platform"] = devs[0].platform

    from flexible_llm_sharding_tpu.config import FrameworkConfig
    from flexible_llm_sharding_tpu.utils.metrics import (
        LiveArrayPeakSampler,
        peak_hbm_gb,
    )

    # Sized so one bench run (incl. first compile) stays in single-digit
    # minutes on one v5e chip, while weights (~0.5 GB) are large enough that
    # the serialized-vs-overlapped difference is the dominant term.
    cfg_kwargs = dict(
        vocab_size=32000,
        hidden_size=1024,
        intermediate_size=2816,
        num_hidden_layers=16 if on_tpu else 4,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=4096,
    )
    model_path = make_model(jax, cfg_kwargs)
    prompts = make_prompts(
        n=8 if on_tpu else 2,
        prefix_words=180,
        suffix_words=24,
        n_suffix=4,
    )
    tok = BenchTokenizer()

    # Host-side pipeline first: accelerator-independent.
    bench_host_stream(result, model_path, budget_left)

    bench_host_cache(result, model_path, budget_left, devs[0])

    def fw(prefetch: int | None) -> FrameworkConfig:
        return FrameworkConfig(
            model_path=model_path,
            layer_num_per_shard=1,
            storage_location="cpu",
            dtype="bfloat16",
            block_size=8,
            prefetch_depth=prefetch,
            disk_folder=os.path.join(BENCH_DIR, "acts"),
        )

    result["device_kind"] = getattr(devs[0], "device_kind", devs[0].platform)

    bench_residency(result, model_path, prompts, tok, budget_left, fw)

    bench_mixedprec(result, model_path, prompts, tok, budget_left, fw)

    bench_trace_overhead(result, prompts, tok, budget_left, fw)

    bench_recorder_overhead(result, prompts, tok, budget_left, fw)

    bench_wal_overhead(result, prompts, tok, budget_left, fw)

    # Deterministic synthetic-clock loop — costs milliseconds.
    bench_fleet_stagger(result)

    # Host->HBM link bandwidth: the binding constraint of weight streaming;
    # makes every throughput number legible.
    from flexible_llm_sharding_tpu.utils.metrics import (
        measure_host_to_hbm_gbps,
    )

    result["host_to_hbm_gbps"] = round(measure_host_to_hbm_gbps(devs[0]), 3)
    log(f"host->HBM link: {result['host_to_hbm_gbps']} GB/s")

    total_tokens = _count_pass_tokens(tok, prompts)

    # The framework's own schedule (auto prefetch: overlapped on TPU; on the
    # CPU backend auto resolves to 0 — there is no host->device link to
    # overlap, and a prefetch thread only contends with XLA:CPU compute).
    cfg_default = fw(None)
    # depth is the configured schedule (branches below key off it); eff is
    # measurement-only — branching on the measured efficiency
    # relied on the prefetch-0 path clamping to exactly 0.0.
    depth = cfg_default.effective_prefetch_depth()
    log(f"framework schedule: effective prefetch depth {depth}")
    # Warmup (compile), then measure the framework schedule FIRST so a later
    # failure still leaves a throughput number in the emitted JSON.
    log("warmup/compile ...")
    run_once(cfg_default, prompts, tok)
    log(f"framework schedule (prefetch={depth}) ...")
    with LiveArrayPeakSampler() as sampler:
        scores, wall_overlap, ex1 = run_once(cfg_default, prompts, tok)
    log(f"  wall={wall_overlap:.2f}s stats={ex1.stats}")
    assert all(np.isfinite(s).all() for s in scores)
    # Second rep, min wall: one host hiccup must not set the record.
    _, wall2, _ = run_once(cfg_default, prompts, tok)
    wall_overlap = min(wall_overlap, wall2)

    peak = peak_hbm_gb()
    if peak is not None:
        result["peak_hbm_gb"] = round(peak, 3)
        result["peak_hbm_source"] = "allocator"  # device memory_stats peak
    elif sampler.peak_bytes:
        # The CPU backend reports no allocator stats; there the live-array
        # peak (weights + activations + prefetch queue, minus XLA scratch)
        # stands in, and is marked as such. On a TPU peak_hbm_gb() raises
        # instead of returning None, so this arm is CPU-only.
        result["peak_hbm_gb"] = round(sampler.peak_gb, 3)
        result["peak_hbm_source"] = "live_arrays"

    # MFU: analytic model FLOPs/token over the chip's peak bf16 FLOP/s.
    # Streaming is transfer-bound, so read this against host_to_hbm_gbps.
    try:
        from flexible_llm_sharding_tpu.config import LlamaConfig
        from flexible_llm_sharding_tpu.utils.metrics import (
            model_flops_per_token,
        )

        mean_ctx = int(
            np.mean([len(tok(p)["input_ids"]) for p, _ in prompts])
        )
        fpt = model_flops_per_token(LlamaConfig(**cfg_kwargs), mean_ctx)
        result["model_flops_per_token"] = round(fpt)
    except Exception:
        log("mfu accounting failed:\n" + traceback.format_exc())
    _set_throughput(result, total_tokens, wall_overlap, devs[0])
    # Compute-window MFU: model FLOPs over the DEVICE-compute seconds of one
    # measured pass (executor stats exclude weight-upload waits): what
    # fraction of chip peak the compute windows themselves hit.
    try:
        from flexible_llm_sharding_tpu.utils.metrics import chip_peak_flops

        cw = ex1.stats.get("compute_wall_s")
        fpt = result.get("model_flops_per_token")
        peak_fl = chip_peak_flops(devs[0])
        if cw and fpt and peak_fl:
            result["mfu_compute"] = round(fpt * total_tokens / cw / peak_fl, 6)
    except Exception:
        log("compute-mfu accounting failed:\n" + traceback.format_exc())

    # Overlap efficiency: what fraction of weight-produce time was hidden
    # under compute in the measured overlapped run (the
    # bench never quantified this). Both terms come from the executor's own
    # direct timers, in the same units: produce_wall_s is the producer's
    # whole per-shard wall (host load + device placement dispatch) and
    # source_wait_s is the driver time blocked on the producer — the part
    # prefetch did NOT hide. Serialized schedule -> wait ≈ all of produce
    # -> efficiency ≈ 0; perfect overlap -> wait ≈ the first shard only ->
    # efficiency -> 1 - 1/n_shards.
    st = ex1.stats
    eff = _overlap_efficiency(st)
    if eff is not None:
        result["overlap_efficiency"] = round(eff, 3)
        result["stream_seconds"] = {
            "produce_wall_s": round(st["produce_wall_s"], 3),
            "load_weights_s": round(st["load_weights_time_s"], 3),
            "source_wait_s": round(st["source_wait_s"], 3),
            "compute_wall_s": round(st["compute_wall_s"], 3),
            "total_wall_s": round(st["total_wall_s"], 3),
        }

    if depth == 0:
        # The platform-tuned schedule IS the serialized reference schedule
        # here (no transfer link to hide) — identical configs, so the true
        # ratio is 1 by construction. The measured ratio of IDENTICAL
        # schedules is this rig's noise floor: ≥5 interleaved reps with
        # dispersion, so every other CPU-derived ratio in the artifact can
        # be read against it (a single-rep 0.758
        # between identical schedules invalidated all CPU ratios).
        log("serialized (prefetch=0) == platform schedule; noise-floor reps ...")
        nf_ratios = []
        for i in range(5):
            _, w_a, _ = run_once(fw(0), prompts, tok)
            _, w_b, _ = run_once(cfg_default, prompts, tok)
            nf_ratios.append(w_a / w_b)
            log(f"  noise pair {i}: {w_a:.2f}s / {w_b:.2f}s = {nf_ratios[-1]:.3f}")
            if budget_left() < 0.55:
                log("  noise-floor budget exhausted; stopping reps")
                break
        result["vs_baseline"] = 1.0
        result["schedules_identical"] = True
        _ratio_stats(result, "measured_ratio", nf_ratios)
        # Even where the platform schedule is serialized (no transfer link
        # to hide, so auto prefetch = 0), one FORCED-prefetch rep records
        # the overlap machinery's own efficiency — the driver is ~never
        # blocked on the producer regardless of platform (measured 0.91-0.95
        # here vs 0.000 serialized). Budget-gated like every optional phase.
        if budget_left() > 0.5:
            try:
                _, _, ex_f = run_once(fw(2), prompts, tok)
                eff_f = _overlap_efficiency(ex_f.stats)
                if eff_f is not None:
                    result["overlap_efficiency_forced"] = round(eff_f, 3)
                    log(
                        "forced-prefetch overlap efficiency: "
                        f"{result['overlap_efficiency_forced']}"
                    )
            except Exception:
                log("forced-prefetch rep failed:\n" + traceback.format_exc())
    else:
        # PAIRED serialized-vs-overlapped reps: back-to-back pairs see ~the
        # same host conditions, and the MEDIAN of per-pair ratios rejects
        # an outlier rep. Time-bounded so a slow link still yields at least
        # one pair inside the deadline.
        log("serialized (prefetch=0, reference schedule), paired reps ...")
        ratios = []
        for i in range(3):
            _, w_ser, _ = run_once(fw(0), prompts, tok)
            _, w_ovl, _ = run_once(cfg_default, prompts, tok)
            ratios.append(w_ser / w_ovl)
            wall_overlap = min(wall_overlap, w_ovl)
            log(f"  pair {i}: serial={w_ser:.2f}s overlap={w_ovl:.2f}s "
                f"ratio={ratios[-1]:.3f}")
            _ratio_stats(result, "vs_baseline", ratios)
            result["overlap_pair_ratios"] = [round(r, 3) for r in ratios]
            if budget_left() < 0.6:
                # Leave the majority of the deadline for the int8 pairs
                # and the pallas/decode phases — a slow link must not
                # starve them.
                log("  schedule-pair budget exhausted; stopping reps")
                break
        # The pairs may have seen a faster link than the headline reps;
        # keep throughput/MFU consistent with the best overlapped wall.
        if total_tokens / wall_overlap > (result["value"] or 0):
            _set_throughput(result, total_tokens, wall_overlap, devs[0])

    # The reference's ACTUAL schedule (per-tensor sync uploads, no scan,
    # per-prompt loop) — measured on both platforms: on CPU the schedule
    # differences (batching, scan, stacked uploads) exist without a link.
    if budget_left() > 0.42:
        try:
            bench_reference_schedule(
                jax, cfg_default, prompts, tok, result, budget_left
            )
        except Exception:
            log("reference-schedule bench failed:\n" + traceback.format_exc())
    else:
        log("skipping reference-schedule bench (deadline budget exhausted)")

    if not on_tpu:
        # int8 streaming compresses the host->HBM link; on the CPU backend
        # there is no such link and the dequant cost dominates (measured
        # 0.84x in r2) — the mode's premise doesn't hold, so the number is
        # only measured on a chip. The SPECULATIVE-MECHANISM ratio below,
        # by contrast, measures a platform-independent structure (accepted
        # drafts halve the weight-stream count), so it still runs here, as
        # a platform=cpu count.
        log("skipping int8 bench on the CPU backend (no host->HBM link)")
        if budget_left() > 0.12:
            try:
                bench_spec(fw(2), tok, result, budget_left)
            except Exception:
                log("spec bench failed:\n" + traceback.format_exc())
        else:
            log("skipping spec bench (deadline budget exhausted)")
        if budget_left() > 0.05:
            try:
                bench_spec_serve(fw(2), tok, result, budget_left)
            except Exception:
                log("spec serve bench failed:\n" + traceback.format_exc())
        else:
            log("skipping spec serve bench (deadline budget exhausted)")
        if budget_left() > 0.04:
            try:
                bench_spec_adaptive(fw(2), tok, result, budget_left)
            except Exception:
                log("spec adaptive bench failed:\n" + traceback.format_exc())
        else:
            log("skipping spec adaptive bench (deadline budget exhausted)")
        if budget_left() > 0.03:
            try:
                bench_kv_reuse(fw(2), tok, result, budget_left)
            except Exception:
                log("kv reuse bench failed:\n" + traceback.format_exc())
        else:
            log("skipping kv reuse bench (deadline budget exhausted)")
        if budget_left() > 0.03:
            try:
                bench_adapters(fw(2), tok, result, budget_left)
            except Exception:
                log("adapter bench failed:\n" + traceback.format_exc())
        else:
            log("skipping adapter bench (deadline budget exhausted)")
        return

    # TPU-only phases from here (the early return above handled CPU).

    def quant_phase() -> None:
        try:
            # int8/int4 weight streaming: same workload, half / a quarter
            # of the bytes over the host->HBM link (the binding constraint
            # of this design) with on-device dequant. The ratios quantify
            # the opt-in transfer-compression modes. TPU-only.
            from flexible_llm_sharding_tpu.utils.checkpoint import (
                NATIVE_LAYOUT_MARKER,
                requantize_native,
            )

            import dataclasses
            import shutil

            def quant_cfg(qdtype: str):
                qpath = f"{model_path}-{qdtype}"
                # The layout marker is written LAST by requantize_native,
                # so a killed/partial conversion never looks complete;
                # rebuild from scratch in that case rather than streaming
                # a broken dir.
                if not os.path.exists(
                    os.path.join(qpath, NATIVE_LAYOUT_MARKER)
                ):
                    shutil.rmtree(qpath, ignore_errors=True)
                    requantize_native(model_path, qpath, dtype=qdtype)
                return dataclasses.replace(fw(2), model_path=qpath)

            # Paired with fresh bf16 runs (same rationale as the schedule
            # pairs).
            # 3 pairs so the median can actually REJECT a link-flip
            # outlier (the median of 2 is their mean — no rejection).
            for qdtype, key, floor in (
                ("int8", "int8_speedup", 0.35),
                ("int4", "int4_speedup", 0.28),
            ):
                if budget_left() < floor:
                    log(f"skipping {qdtype} bench (deadline budget exhausted)")
                    continue
                try:  # per-dtype isolation: int8 failure must not kill int4
                    qc = quant_cfg(qdtype)
                    run_once(qc, prompts, tok)  # warm/compile
                    ratios = []
                    for i in range(3):
                        _, wall_q, _ = run_once(qc, prompts, tok)
                        _, w_bf16, _ = run_once(cfg_default, prompts, tok)
                        ratios.append(w_bf16 / wall_q)
                        log(f"{qdtype} pair {i}: q={wall_q:.2f}s "
                            f"bf16={w_bf16:.2f}s ratio={ratios[-1]:.3f}")
                        _ratio_stats(result, key, ratios)
                        if budget_left() < floor:
                            log(f"{qdtype} pair budget exhausted; "
                                "stopping reps")
                            break
                except Exception:
                    log(f"{qdtype} bench failed:\n" + traceback.format_exc())
        except Exception:
            log("quantized bench setup failed:\n" + traceback.format_exc())

    def pallas_phase() -> None:
        try:
            bench_pallas(jax, result)
        except Exception:
            log("pallas bench failed:\n" + traceback.format_exc())

    def decode_phase() -> None:
        try:
            # Small prompt set: the recompute baseline costs n_tok full
            # streaming passes, twice (warmup + measure).
            bench_decode(fw(2), prompts[:2], tok, result)
        except Exception:
            log("decode bench failed:\n" + traceback.format_exc())

    def resident_phase() -> None:
        if budget_left() > 0.15:
            try:
                bench_resident_mfu(jax, result, budget_left)
            except Exception:
                log("resident MFU bench failed:\n" + traceback.format_exc())
        else:
            log("skipping resident MFU bench (deadline budget exhausted)")

    def spec_phase() -> None:
        if budget_left() > 0.12:
            try:
                bench_spec(fw(2), tok, result, budget_left)
            except Exception:
                log("spec bench failed:\n" + traceback.format_exc())
        else:
            log("skipping spec bench (deadline budget exhausted)")
        if budget_left() > 0.05:
            try:
                bench_spec_serve(fw(2), tok, result, budget_left)
            except Exception:
                log("spec serve bench failed:\n" + traceback.format_exc())
        else:
            log("skipping spec serve bench (deadline budget exhausted)")
        if budget_left() > 0.03:
            try:
                bench_kv_reuse(fw(2), tok, result, budget_left)
            except Exception:
                log("kv reuse bench failed:\n" + traceback.format_exc())
        else:
            log("skipping kv reuse bench (deadline budget exhausted)")
        if budget_left() > 0.03:
            try:
                bench_adapters(fw(2), tok, result, budget_left)
            except Exception:
                log("adapter bench failed:\n" + traceback.format_exc())
        else:
            log("skipping adapter bench (deadline budget exhausted)")

    for phase_fn in (
        quant_phase, pallas_phase, decode_phase, resident_phase, spec_phase,
    ):
        phase_fn()


def run_gb_bench(
    model_path: str,
    n_prompts: int = 2,
    out: str | None = None,
    quant: bool = True,
) -> dict:
    """GB-scale bench : the streamed-scoring phase,
    ``vs_reference_schedule``, a forced-prefetch overlap-efficiency rep,
    and int8/int4 ratios against a REAL multi-GB checkpoint (the pre-split
    per-layer directory given by ``--model_path``) instead of the toy bench model. Toy
    ratios (~0.5 GB, 488 MFLOPs/token) don't establish behaviour in the
    regime the framework exists for — GB passes are where stacking, cast
    throughput, readahead and quantized streaming actually bind.

    Honesty rules carried over from the toy bench: single/few reps are
    flagged by ``*_n`` + ``*_inconclusive`` (a GB pass costs ~minutes, so
    dispersion is bought sparingly); on the CPU backend the int8/int4
    ratios measure dequant cost, not link compression, and say so.
    Deadline: ``BENCH_GB_DEADLINE_S`` (default 7200s), budget-gating each
    optional phase like the toy bench.
    """
    t0_all = time.perf_counter()
    deadline_s = float(os.environ.get("BENCH_GB_DEADLINE_S", "7200"))

    def budget_left() -> float:
        if deadline_s <= 0:
            return 1.0
        return 1.0 - (time.perf_counter() - t0_all) / deadline_s

    jax, devs = _init_jax()
    from flexible_llm_sharding_tpu.config import FrameworkConfig
    from flexible_llm_sharding_tpu.utils import checkpoint as ckpt_mod

    model_bytes = sum(
        os.path.getsize(os.path.join(model_path, f))
        for f in os.listdir(model_path)
        if f.endswith(ckpt_mod.LAYER_FILE_SUFFIX)
    )
    result: dict = {
        "metric": "gb_streamed_scoring",
        "model_path": model_path,
        "model_gb": round(model_bytes / 1e9, 2),
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", devs[0].platform),
        "prompts": n_prompts,
    }
    tok = BenchTokenizer()
    prompts = make_prompts(
        n=n_prompts, prefix_words=700, suffix_words=24, n_suffix=4
    )
    total_tokens = _count_pass_tokens(tok, prompts)
    result["tokens_per_pass"] = total_tokens

    # Rep accumulation across invocations: a GB quant pair costs ~3 passes
    # (~minutes each), so one run records a single flagged-inconclusive
    # ratio; a LATER run against the same model/workload/platform merges
    # its fresh pair with the prior run's raw ratios (persisted as
    # gb_*_ratios) and the median/spread/n upgrade honestly instead of
    # resetting to n=1 forever.
    prior_ratios: dict[str, list] = {}
    if out and os.path.exists(out):
        try:
            with open(out) as f:
                prior = json.load(f)
            if (
                prior.get("model_path") == model_path
                and prior.get("tokens_per_pass") == total_tokens
                and prior.get("platform") == result["platform"]
                and not prior.get("partial")
            ):
                for q in ("int8", "int4"):
                    if isinstance(prior.get(f"gb_{q}_ratios"), list):
                        prior_ratios[q] = list(prior[f"gb_{q}_ratios"])
                    elif (
                        prior.get(f"gb_{q}_speedup") is not None
                        and prior.get(f"gb_{q}_speedup_n") == 1
                    ):
                        # Pre-ratios-list artifact: a single-rep median IS
                        # the raw ratio, so accumulation still works
                        # against captures made before the lists existed.
                        prior_ratios[q] = [prior[f"gb_{q}_speedup"]]
                    if q in prior_ratios:
                        # Seed the result with the prior reps UP FRONT: if
                        # this run's quant phase is budget-skipped or
                        # fails, the finally-emit must carry the prior
                        # measurement forward, not destroy it (the merge
                        # site overwrites these when it actually runs, and
                        # only then claims merged_reps_from).
                        result[f"gb_{q}_ratios"] = prior_ratios[q]
                        _ratio_stats(
                            result, f"gb_{q}_speedup", prior_ratios[q]
                        )
                if prior_ratios:
                    result["gb_reps_carried_from"] = prior.get(
                        "captured_at", "prior run"
                    )
                    prior_ratios["_from"] = result["gb_reps_carried_from"]
        except (OSError, ValueError):
            pass
    result["captured_at"] = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
    )

    # GB passes cost minutes-to-hours; the deadline or a phase crash must
    # not lose what WAS measured. emit() is idempotent-ish: the watchdog's
    # partial emission or the finally's final one.
    import threading

    def emit(partial: bool = False) -> None:
        snap = dict(result)
        if partial:
            snap["partial"] = True
        target = out
        if partial and out and os.path.exists(out):
            # A deadline-partial must never DEGRADE the artifact of
            # record: if a complete capture already sits at `out`, the
            # partial goes to a sidecar instead (the 16:42Z partial
            # overwrote a complete committed capture before this guard).
            try:
                with open(out) as f:
                    if not json.load(f).get("partial"):
                        target = out + ".partial"
                        log(f"complete artifact at {out} preserved; "
                            f"partial emission -> {target}")
            except (OSError, ValueError):
                pass
        if target:
            try:
                with open(target, "w") as f:
                    json.dump(snap, f, indent=1)
            except OSError as e:
                log(f"could not write {target}: {e!r}")
        print(json.dumps(snap), flush=True)

    def gb_watchdog():
        time.sleep(deadline_s if deadline_s > 0 else 86400)
        reason = "deadline hit"
        log(f"GB watchdog: {reason}; emitting partial result")
        emit(partial=True)
        os._exit(1)

    threading.Thread(target=gb_watchdog, daemon=True).start()

    def fw(prefetch: int | None, path: str = model_path) -> FrameworkConfig:
        return FrameworkConfig(
            model_path=path,
            layer_num_per_shard=1,
            storage_location="cpu",
            dtype="bfloat16",
            block_size=8,
            prefetch_depth=prefetch,
            disk_folder=os.path.join(BENCH_DIR, "gb_acts"),
        )

    cfg_default = fw(None)
    log(f"GB bench: {result['model_gb']} GB model, {total_tokens} tokens, "
        f"platform={result['platform']}")
    try:
        _run_gb_phases(
            jax, devs, result, cfg_default, fw, prompts, tok, total_tokens,
            model_path, quant, budget_left, prior_ratios,
        )
    finally:
        result["gb_wall_total_s"] = round(time.perf_counter() - t0_all, 1)
        emit()
    return result


def _run_gb_phases(
    jax, devs, result, cfg_default, fw, prompts, tok, total_tokens,
    model_path, quant, budget_left, prior_ratios=None,
) -> None:
    from flexible_llm_sharding_tpu.utils import checkpoint as ckpt_mod
    from flexible_llm_sharding_tpu.utils.metrics import peak_hbm_gb

    # No separate warmup pass at GB scale (a pass costs minutes); the first
    # measured rep carries compile time and is marked.
    _, wall1, _ = run_once(cfg_default, prompts, tok)
    result["first_pass_s_includes_compile"] = round(wall1, 1)
    _, wall, ex2 = run_once(cfg_default, prompts, tok)
    result["gb_tokens_per_sec"] = round(total_tokens / wall, 3)
    result["gb_pass_s"] = round(wall, 1)
    st = ex2.stats
    result["gb_stream_seconds"] = {
        k: round(st[k], 3)
        for k in (
            "load_weights_time_s", "compute_wall_s", "source_wait_s",
            "total_wall_s",
        )
        if k in st
    }
    if st.get("streamed_bytes"):
        result["gb_streamed_bytes_per_pass"] = int(st["streamed_bytes"])
    peak = peak_hbm_gb()
    if peak is not None:
        result["gb_peak_hbm_gb"] = round(peak, 3)
        result["gb_peak_hbm_source"] = "allocator"

    # Overlap at GB scale: force prefetch and read the executor's own
    # produce/wait timers (PROJECTION.json's first what-must-be-true).
    if budget_left() > 0.75:
        _, _, ex_f = run_once(fw(2), prompts, tok)
        eff = _overlap_efficiency(ex_f.stats)
        if eff is not None:
            result["gb_overlap_efficiency_forced"] = round(eff, 3)
            log(f"GB forced-prefetch overlap efficiency: {eff:.3f}")

    # The reference's own schedule at GB scale (per-tensor sync uploads,
    # no scan, per-prompt loop) — bench_reference_schedule budget-gates
    # its reps and flags single-rep dispersion via _ratio_stats.
    if budget_left() > 0.5:
        gb_ref: dict = {}
        try:
            bench_reference_schedule(
                jax, cfg_default, prompts, tok, gb_ref, budget_left
            )
        except Exception:
            log("GB reference-schedule bench failed:\n"
                + traceback.format_exc())
        finally:
            # bench_reference_schedule writes incrementally after each
            # pair: a crash on pair 2 must not drop pair 1's GB-pass-cost
            # measurement.
            result.update({f"gb_{k}": v for k, v in gb_ref.items()})

    # int8/int4 at GB scale. On CPU there is no host->HBM link to
    # compress, so the ratio measures cast+dequant cost — recorded, with
    # the premise note, because GB-scale cast/readahead behaviour is
    # exactly what the toy capture could not establish.
    if quant:
        if devs[0].platform == "cpu":
            result["gb_quant_note"] = (
                "cpu backend: no host->HBM link — ratios measure host "
                "cast + on-device dequant cost, not link compression"
            )
        for qdtype, key, floor in (
            ("int8", "gb_int8_speedup", 0.3),
            ("int4", "gb_int4_speedup", 0.15),
        ):
            if budget_left() < floor:
                log(f"skipping GB {qdtype} (budget)")
                continue
            try:
                qpath = f"{model_path}-{qdtype}"
                qmarker = os.path.join(qpath, ckpt_mod.NATIVE_LAYOUT_MARKER)
                src_marker = os.path.join(
                    model_path, ckpt_mod.NATIVE_LAYOUT_MARKER
                )
                # Rebuild on a STALE cache too: model_path is a real,
                # user-supplied checkpoint that can be re-split between
                # runs; its layout marker is written last by the splitter,
                # so a quant dir older than it was built from different
                # weights and would make the ratio compare two models.
                fresh = os.path.exists(qmarker) and (
                    not os.path.exists(src_marker)
                    or os.path.getmtime(qmarker)
                    >= os.path.getmtime(src_marker)
                )
                if not fresh:
                    import shutil

                    shutil.rmtree(qpath, ignore_errors=True)
                    tq = time.perf_counter()
                    ckpt_mod.requantize_native(
                        model_path, qpath, dtype=qdtype
                    )
                    result[f"gb_{qdtype}_requantize_s"] = round(
                        time.perf_counter() - tq, 1
                    )
                qc = fw(None, qpath)
                _, wq1, _ = run_once(qc, prompts, tok)  # compile rep
                _, wq, exq = run_once(qc, prompts, tok)
                _, wb, _ = run_once(cfg_default, prompts, tok)  # fresh pair
                ratios = (prior_ratios or {}).get(qdtype, []) + [wb / wq]
                result[f"gb_{qdtype}_ratios"] = [
                    round(r, 4) for r in ratios
                ]
                _ratio_stats(result, key, ratios)
                if (prior_ratios or {}).get(qdtype):
                    # Claimed only where the merge actually happened.
                    result["merged_reps_from"] = prior_ratios["_from"]
                if exq.stats.get("streamed_bytes"):
                    result[f"gb_{qdtype}_streamed_bytes"] = int(
                        exq.stats["streamed_bytes"]
                    )
                log(f"GB {qdtype}: quant={wq:.1f}s bf16={wb:.1f}s "
                    f"ratio={wb / wq:.3f}")
            except Exception:
                log(f"GB {qdtype} failed:\n" + traceback.format_exc())


def main() -> None:
    if "--model_path" in sys.argv:
        i = sys.argv.index("--model_path")
        model_path = sys.argv[i + 1]
        n_prompts = 2
        if "--prompts" in sys.argv:
            n_prompts = int(sys.argv[sys.argv.index("--prompts") + 1])
        out = None
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        run_gb_bench(model_path, n_prompts=n_prompts, out=out)
        return

    result = {
        "metric": "streamed_scoring_throughput",
        "value": None,
        "unit": "tokens/sec",
        "vs_baseline": None,
    }

    try:
        run_bench(result)
    except Exception:
        log("bench failed:\n" + traceback.format_exc())
        result["error"] = traceback.format_exc(limit=1).strip().splitlines()[-1]
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["value"] is not None else 1)


if __name__ == "__main__":
    main()
