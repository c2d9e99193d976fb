"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the normal entry points at the full width
of Llama-3-8B (hidden 4096, intermediate 14336, 32 q / 8 kv heads, head 128,
vocabulary 128256, rope theta 500000, bf16). Depth is the only cut: 8 decoder
layers, ~5.6 GB of per-layer files, so a sweep streams several shards.
Weights are random, made from ``--seed``; the tokenizer is the word-hash one
(no network, no tokenizer assets) handed to ``cli.main(argv, tokenizer=...)``.

One chip (no arguments, as the driver runs it):
  device report -> HF-layout checkpoint from the seed -> ``prepare_weights.py``
  -> ``main.py verify`` -> offline scoring (storage cpu, then tpu and disk in a
  second process that must hit the compile cache) checked against
  ``llama.forward_full`` in float32 -> KV decode -> ``serve`` with staggered
  arrivals, generations equal to the KV decode's up to bf16 near-ties ->
  proof from the compiled steps that the Pallas kernels are in them
  (``tpu_custom_call``).

``--chips 4`` (run by the builder): the one-chip scoring it compares with,
the same prompts as MP pipeline, ``--data_parallel`` and ``--tensor_parallel
4`` with a per-device check, and ``serve --replicas 4``; no other phase.

One process per chip: this parent never imports JAX; children take the chip
one after another. Every phase is a hard failure. Without a TPU the script
exits non-zero and prints no result; ``--cpu-rehearsal`` is the explicit
toy-size CPU run, which reports ``platform: cpu`` truthfully. Every line on
stdout is one JSON object; timings are smoke timings, not benchmark numbers.
The last line is ``{"ok": true, "device": {...}}`` and nothing more.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import pickle
import shutil
import subprocess
import sys
import time
import zlib

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

WORK = os.path.join(ROOT, "chip_smoke_tmp")  # listed in .gitignore


class WordHashTokenizer:
    """Deterministic word-hash tokenizer (no model assets needed)."""

    BOS, EOS, VOCAB = 1, 2, 32000

    eos_token = "</s>"
    pad_token = "</s>"
    pad_token_id = EOS
    padding_side = "right"

    def _one_id(self, w: str) -> int:
        # decode()'s output round-trips, so the full-recompute generation
        # loop, which rebuilds its strings, retokenizes a generated token to
        # the same id: the recompute-against-KV comparison rests on it.
        if w.startswith("tok") and w[3:].isdigit():
            return int(w[3:]) % self.VOCAB
        # crc32, not hash(): Python's hash() is salted per process, and the
        # children must all see the same ids.
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


def make_prompts(n: int, prefix_words: int, suffix_words: int, n_suffix: int):
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(5000)]

    def text(k):
        return " ".join(rng.choice(words, size=k))

    return [
        (text(prefix_words), tuple(text(suffix_words) for _ in range(n_suffix)))
        for _ in range(n)
    ]


# Published Llama-3-8B widths (meta-llama/Meta-Llama-3-8B config.json); only
# num_hidden_layers is cut.
LLAMA3_8B = dict(
    hidden_size=4096,
    intermediate_size=14336,
    num_attention_heads=32,
    num_key_value_heads=8,
    vocab_size=128256,
    rope_theta=500000.0,
    max_position_embeddings=8192,
    rms_norm_eps=1e-5,
)
# --cpu-rehearsal only: control flow at a size the CPU backend finishes.
TOY = dict(
    hidden_size=128,
    intermediate_size=256,
    num_attention_heads=8,
    num_key_value_heads=4,
    vocab_size=WordHashTokenizer.VOCAB,
    rope_theta=500000.0,
    max_position_embeddings=8192,
    rms_norm_eps=1e-5,
)

GEN_TOKENS = 8  # KV-decode and serve generate this many tokens per suffix
ORACLE_PROMPTS = 2  # prompts compared with the float32 forward_full

# How close two score arrays must be, in log-probability over the
# reference's TOP_K most likely tokens. Seeded N(0, 0.02) weights give a
# nearly flat softmax over 128256 entries (top probability ~1e-3), so an
# absolute bound on probabilities says nothing (the verify skill's 2e-5 is for
# toy models) and the bound is relative. bf16 activations through 8 layers at
# these widths put ~0.1 of noise on logits whose std is 1.28. Measured max
# |dlogp| against the float32 forward_full (PR 21, PERF.md Findings): 0.118
# on the v5e, 0.156 on the CPU backend at full width; the bound is 3x the
# chip's. The same noise breaks near-ties: on the CPU 1 of 8 rows picked the
# reference's SECOND token where its top-2 gap was 0.043, and on the v5e 9 of
# 32 served generations left the offline ones at gaps up to 0.094 (waves of 2
# and the offline block of 8 are differently shaped bf16 programs; the CPU
# backend gives identical tokens). So "argmax identical" is held up to this
# tolerance: a pick must be the reference's, or within LOGP_TOL of it by the
# reference's own log-probabilities.
LOGP_TOL = 0.35
TOP_K = 100


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Checkpoint from a seed (parent process; numpy only)
# ---------------------------------------------------------------------------

def build_hf_checkpoint(model: dict, layers: int, seed: int, hf_dir: str) -> int:
    """Write a sharded HF-layout bf16 safetensors checkpoint (one shard file
    per decoder layer; embed rides with layer 0, norm + head with the last)
    so ``prepare_weights.py`` loads shards incrementally the way it does for
    a real multi-shard checkpoint. Returns total weight bytes."""
    import ml_dtypes
    from safetensors.numpy import save_file

    from concurrent.futures import ThreadPoolExecutor

    os.makedirs(hf_dir, exist_ok=True)
    bf16 = np.dtype(ml_dtypes.bfloat16)
    h, inter, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    kv = h // model["num_attention_heads"] * model["num_key_value_heads"]

    def shard(i: int) -> tuple[str, list[str], int]:
        # One generator per layer, so layers build in parallel threads (numpy
        # fills release the GIL) and the bytes depend only on (seed, i).
        rng = np.random.default_rng([seed, i])

        def rand(*shape):
            x = rng.standard_normal(shape, dtype=np.float32)
            x *= 0.02
            return x.astype(bf16)

        p = f"model.layers.{i}"
        sd = {
            f"{p}.self_attn.q_proj.weight": rand(h, h),
            f"{p}.self_attn.k_proj.weight": rand(kv, h),
            f"{p}.self_attn.v_proj.weight": rand(kv, h),
            f"{p}.self_attn.o_proj.weight": rand(h, h),
            f"{p}.mlp.gate_proj.weight": rand(inter, h),
            f"{p}.mlp.up_proj.weight": rand(inter, h),
            f"{p}.mlp.down_proj.weight": rand(h, inter),
            f"{p}.input_layernorm.weight": np.ones(h, dtype=bf16),
            f"{p}.post_attention_layernorm.weight": np.ones(h, dtype=bf16),
        }
        if i == 0:
            sd["model.embed_tokens.weight"] = rand(v, h)
        if i == layers - 1:
            sd["model.norm.weight"] = np.ones(h, dtype=bf16)
            sd["lm_head.weight"] = rand(v, h)
        fn = f"model-{i + 1:05d}-of-{layers:05d}.safetensors"
        save_file(sd, os.path.join(hf_dir, fn))
        return fn, list(sd), sum(a.nbytes for a in sd.values())

    weight_map: dict[str, str] = {}
    total = 0
    with ThreadPoolExecutor(max_workers=min(layers, os.cpu_count() or 1)) as pool:
        for fn, keys, nbytes in pool.map(shard, range(layers)):
            weight_map.update(dict.fromkeys(keys, fn))
            total += nbytes
    with open(os.path.join(hf_dir, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    with open(os.path.join(hf_dir, "config.json"), "w") as f:
        json.dump(
            {
                "model_type": "llama",
                "architectures": ["LlamaForCausalLM"],
                "torch_dtype": "bfloat16",
                "tie_word_embeddings": False,
                "num_hidden_layers": layers,
                **model,
            },
            f,
        )
    return total


# ---------------------------------------------------------------------------
# Children: each takes the chip, runs one phase, prints JSON lines
# ---------------------------------------------------------------------------

class _Recorder:
    """Stands in for one module-level jitted step: forwards every call and
    remembers each distinct argument signature, so the very program that ran
    can be lowered and compiled again (a cache hit) and its text inspected."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.sigs: dict[str, tuple] = {}

    def __call__(self, *args, **kwargs):
        import jax

        def spec(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if isinstance(x, np.ndarray):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        sig = jax.tree.map(spec, (args, kwargs))
        self.sigs.setdefault(repr(sig), sig)
        return self.jitted(*args, **kwargs)

    def custom_calls(self) -> list[int]:
        return [
            self.jitted.lower(*a, **k).compile().as_text().count("tpu_custom_call")
            for a, k in self.sigs.values()
        ]


def _record(targets: list[tuple[str, str]]) -> dict[str, _Recorder]:
    """Swap ``module.attr`` jitted steps for recorders. ``targets`` name the
    attribute in the module whose namespace the CALLER resolves it from."""
    import importlib

    recs: dict[str, _Recorder] = {}
    for mod_name, attr in targets:
        mod = importlib.import_module(f"flexible_llm_sharding_tpu.{mod_name}")
        rec = recs[f"{mod_name}.{attr}"] = _Recorder(getattr(mod, attr))
        setattr(mod, attr, rec)
    return recs


def _kernel_proof(recs: dict[str, _Recorder], use_pallas: bool, platform: str) -> dict:
    """tpu_custom_call counts of every recorded step that ran. On a TPU with
    kernels resolved on, a step that ran without one is the hidden fallback
    this script exists to catch."""
    proof = {}
    for name, rec in recs.items():
        if rec.sigs:
            proof[name] = rec.custom_calls()
    if platform == "tpu":
        if not use_pallas:
            raise SystemExit("use_pallas resolved to False on a TPU")
        if not proof:
            raise SystemExit("no kernel-bearing step ran in this phase")
        missing = [n for n, counts in proof.items() if min(counts) == 0]
        if missing:
            raise SystemExit(
                f"steps ran with use_pallas on but no tpu_custom_call in "
                f"their compiled text: {missing} ({proof})"
            )
    return proof


class _Compiles:
    """Compile seconds and persistent-cache hits/misses of this process, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.events: dict[str, int] = {}
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs

    def _ev(self, name, **kw):
        if "compilation_cache" in name:
            key = name.rsplit("/", 1)[-1]
            self.events[key] = self.events.get(key, 0) + 1

    def report(self) -> dict:
        from flexible_llm_sharding_tpu.utils.compile_cache import (
            compile_cache_dir,
            compile_cache_entries,
        )

        return {
            "compile_s": round(self.seconds, 2),
            "cache_dir": compile_cache_dir(),
            "cache_entries": compile_cache_entries(),
            "cache_hits": self.events.get("cache_hits", 0),
            "cache_misses": self.events.get("cache_misses", 0),
        }


def _child_setup(a):
    """First JAX use of a child: place the compile cache, then require the
    platform the run was meant for."""
    from flexible_llm_sharding_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax

    devs = jax.devices()
    want = "cpu" if a.cpu_rehearsal else "tpu"
    if devs[0].platform != want:
        raise SystemExit(
            f"platform is {devs[0].platform!r}, this run needs {want!r} "
            "(a CPU run is only ever the explicit --cpu-rehearsal)"
        )
    if len(devs) < a.chips:
        raise SystemExit(f"{len(devs)} devices, --chips {a.chips} asked")
    return jax, devs


@contextlib.contextmanager
def _stderr_kept():
    """stderr still flows, and is kept (yielded as a StringIO) for parsing
    the CLI's final stats line."""
    real, kept = sys.stderr, io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, s):
            real.write(s)
            kept.write(s)
            return len(s)

        def flush(self):
            real.flush()

    with contextlib.redirect_stderr(Tee()):
        yield kept


def _last_json(text: str, must_have: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{") and must_have in line:
            return json.loads(line)
    raise SystemExit(f"no stats line with {must_have!r} on stderr")


def _load(path):
    with open(path, "rb") as f:
        return pickle.load(f)


def _score_argv(a, out: str, storage: str = "cpu", extra: tuple = ()) -> list[str]:
    return [
        "--model_path", a.native_dir,
        "--prompt_pickle", os.path.join(a.work, "prompts.pkl"),
        "--output_file", os.path.join(a.work, out),
        "--storage_location", storage,
        "--disk_folder", os.path.join(a.work, "acts"),
        *extra,
    ]


def _run_cli(argv: list[str]) -> dict:
    """``cli.main`` in this process with the word-hash tokenizer; returns the
    final stats line the CLI printed on stderr."""
    from flexible_llm_sharding_tpu import cli

    t0 = time.perf_counter()
    with _stderr_kept() as err:
        cli.main(argv, tokenizer=WordHashTokenizer())
    stats = _last_json(err.getvalue(), '"wall_s"')
    stats["smoke_wall_s"] = round(time.perf_counter() - t0, 2)
    return stats


def _resolved(argv: list[str]) -> dict:
    """What the flags resolve to on THIS machine (not their defaults)."""
    from flexible_llm_sharding_tpu import cli
    from flexible_llm_sharding_tpu.config import LlamaConfig

    cfg = cli.config_from_args(cli.build_parser().parse_args(argv))
    model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
    return {
        "use_pallas": cfg.pallas_enabled(),
        "prefetch_depth": cfg.effective_prefetch_depth(),
        "decode_resident": cfg.decode_resident_enabled(model_cfg),
    }


def _compare(got, want, what: str) -> dict:
    """Per-prompt [S, T, V] probability arrays against a reference: finite,
    same shape, every pick the reference's own or within LOGP_TOL of it, and
    |dlogp| <= LOGP_TOL over the reference's TOP_K tokens."""
    rows = same = 0
    worst_logp = worst_pick = worst_abs = 0.0
    for g, w in zip(got, want, strict=True):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape or not np.isfinite(g).all():
            raise SystemExit(f"{what}: shape {g.shape} vs {w.shape} or non-finite")
        g, w = g.reshape(-1, g.shape[-1]), w.reshape(-1, w.shape[-1])
        top = np.argsort(-w, axis=-1)[:, :TOP_K]
        lg = np.log(np.take_along_axis(g, top, -1))
        lw = np.log(np.take_along_axis(w, top, -1))
        worst_logp = max(worst_logp, float(np.abs(lg - lw).max()))
        pick = np.take_along_axis(w, g.argmax(-1)[:, None], -1)[:, 0]
        worst_pick = max(worst_pick, float((lw[:, 0] - np.log(pick)).max()))
        worst_abs = max(worst_abs, float(np.abs(g - w).max()))
        rows += len(g)
        same += int((g.argmax(-1) == w.argmax(-1)).sum())
    out = {
        "rows": rows, "argmax_identical": same,
        "worst_pick_logp_gap": worst_pick, "max_abs_logp_diff": worst_logp,
        "max_abs_prob_diff": worst_abs, "logp_tol": LOGP_TOL,
    }
    if worst_pick > LOGP_TOL or worst_logp > LOGP_TOL:
        raise SystemExit(f"{what}: over tolerance: {out}")
    return out


def _compare_generations(got, want, what: str) -> dict:
    """Two greedy generations, per-prompt [S, T, V]. A step is comparable
    while the tokens fed so far agree, i.e. through each suffix's first
    differing pick; over those steps the distributions must agree as
    ``_compare`` demands, so a differing pick is a near-tie by the
    reference's own log-probabilities. Once a near-tie has broken the other
    way the two contexts differ, and the rest of that suffix is excused."""
    g_rows, w_rows, same = [], [], 0
    for g, w in zip(got, want, strict=True):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            raise SystemExit(f"{what}: shape {g.shape} vs {w.shape}")
        differ = g.argmax(-1) != w.argmax(-1)  # [S, T]
        for s in range(g.shape[0]):
            n = int(differ[s].argmax()) + 1 if differ[s].any() else g.shape[1]
            same += not differ[s].any()
            g_rows.append(g[s, :n])
            w_rows.append(w[s, :n])
    out = _compare([np.concatenate(g_rows)], [np.concatenate(w_rows)], what)
    out["generations"] = len(g_rows)
    out["generations_identical"] = int(same)
    return out


def child_device(a) -> None:
    jax, devs = _child_setup(a)
    from flexible_llm_sharding_tpu.utils import metrics, native
    from flexible_llm_sharding_tpu.utils.compile_cache import (
        compile_cache_dir,
        compile_cache_entries,
    )

    so = os.path.join(ROOT, "native", "build", "fls_native.so")
    so_before = os.path.getmtime(so) if os.path.exists(so) else None
    stats = devs[0].memory_stats() or {}
    emit(
        phase="device",
        device={
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
        memory_stats_keys=sorted(stats),
        bytes_limit=stats.get("bytes_limit"),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        chip_hbm_gb=metrics.chip_hbm_gb(devs[0]),
        chip_peak_flops=metrics.chip_peak_flops(devs[0]),
        host_to_hbm_gbps=round(metrics.measure_host_to_hbm_gbps(devs[0]), 3),
        native_loaded=native.native_loaded(),
        native_built_this_run=(
            os.path.exists(so) and os.path.getmtime(so) != so_before
        ),
        compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        cache_dir=compile_cache_dir(),
        cache_entries=compile_cache_entries(),
        cpu_count=os.cpu_count(),
    )


def _oracle(a, jax, scores) -> dict:
    """``llama.forward_full`` in float32 on the same weights, for the first
    ORACLE_PROMPTS prompts: softmax at each suffix's last real token."""
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama
    from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer
    from flexible_llm_sharding_tpu.utils import checkpoint

    cfg = LlamaConfig.from_pretrained(a.native_dir)
    names = checkpoint.layer_names_for(cfg.num_hidden_layers)
    trees = [jax.device_put(checkpoint.load_layer(a.native_dir, n)) for n in names]
    params = {
        "embed": trees[0], "layers": trees[1:-2], "norm": trees[-2],
        "lm_head": trees[-1],
    }
    fwd = jax.jit(
        lambda p, ids: jax.nn.softmax(
            llama.forward_full(p, cfg, ids, dtype=jnp.float32)[0, -1]
        )
    )
    tok = PromptTokenizer(WordHashTokenizer())
    prompts = _load(os.path.join(a.work, "prompts.pkl"))[:ORACLE_PROMPTS]
    want = []
    for prefix, suffixes in prompts:
        t = tok(prefix, suffixes)
        rows = []
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            ids = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            rows.append(np.asarray(fwd(params, jnp.asarray(ids))))
        want.append(np.stack(rows)[:, None, :])
    return _compare(
        scores[:ORACLE_PROMPTS], want,
        "streamed bf16 scores vs float32 forward_full",
    )


def child_score(a) -> None:
    """Offline scoring, storage cpu (the cold process), kernel proof, oracle."""
    jax, devs = _child_setup(a)
    compiles = _Compiles()
    recs = _record([("runtime.executor", "_decoder_block")])
    argv = _score_argv(
        a, "scores_cpu.pkl", extra=("--num_devices", "1") if a.chips > 1 else ()
    )
    resolved = _resolved(argv)
    stats = _run_cli(argv)
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    proof = _kernel_proof(recs, resolved["use_pallas"], devs[0].platform)
    emit(
        phase="score", storage="cpu", resolved=resolved, kernel_proof=proof,
        stats=stats, peak_bytes_in_use=peak,
        **compiles.report(),
    )
    t0 = time.perf_counter()
    oracle = _oracle(a, jax, _load(os.path.join(a.work, "scores_cpu.pkl")))
    emit(phase="oracle", smoke_wall_s=round(time.perf_counter() - t0, 2), **oracle)


def child_score_alt(a) -> None:
    """Second process: storage tpu and disk, identical to cpu; the programs
    are the first process's, so the persistent cache must hit."""
    jax, devs = _child_setup(a)
    compiles = _Compiles()
    want = _load(os.path.join(a.work, "scores_cpu.pkl"))
    for storage in ("tpu", "disk"):
        stats = _run_cli(_score_argv(a, f"scores_{storage}.pkl", storage))
        got = _load(os.path.join(a.work, f"scores_{storage}.pkl"))
        for g, w in zip(got, want, strict=True):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise SystemExit(f"storage {storage}: scores differ from cpu")
        emit(phase="score", storage=storage, identical_to_cpu=True, stats=stats)
    rep = compiles.report()
    if devs[0].platform == "tpu" and rep["cache_hits"] == 0:
        raise SystemExit(f"second process saw no compile-cache hit: {rep}")
    emit(phase="compile_cache_second_process", **rep)


def child_decode(a) -> None:
    jax, devs = _child_setup(a)
    compiles = _Compiles()
    recs = _record([
        ("runtime.decode", "_prefill_decoders"),
        ("runtime.decode", "_decode_decoders"),
        ("runtime.decode", "_fused_decode_steps"),
    ])
    argv = _score_argv(
        a, "scores_decode.pkl",
        extra=("--kv_cache", "true", "--num_gen_token", str(GEN_TOKENS)),
    )
    resolved = _resolved(argv)
    stats = _run_cli(argv)
    scores = _load(os.path.join(a.work, "scores_decode.pkl"))
    for s in scores:
        s = np.asarray(s)
        if s.shape[1] != GEN_TOKENS or not np.isfinite(s).all():
            raise SystemExit(f"decode scores shape {s.shape} / non-finite")
    # Step 0 of the KV decode is the scoring pass's distribution.
    first = _compare(
        [np.asarray(s)[:, :1] for s in scores],
        _load(os.path.join(a.work, "scores_cpu.pkl")),
        "KV decode step 0 vs offline scoring",
    )
    proof = _kernel_proof(recs, resolved["use_pallas"], devs[0].platform)
    emit(
        phase="decode", resolved=resolved, kernel_proof=proof, stats=stats,
        step0_vs_scoring=first, **compiles.report(),
    )


def _serve(a, out: str, extra: tuple, recs_targets) -> tuple[dict, dict]:
    from flexible_llm_sharding_tpu import cli

    recs = _record(recs_targets)
    argv = [
        "serve",
        "--model_path", a.native_dir,
        "--prompt_pickle", os.path.join(a.work, "serve_prompts.pkl"),
        "--output_file", os.path.join(a.work, out),
        "--max_wave_requests", "2",
        "--stagger_ms", "150",
        *extra,
    ]
    t0 = time.perf_counter()
    with _stderr_kept() as err:
        cli.main(argv, tokenizer=WordHashTokenizer())
    stats = _last_json(err.getvalue(), '"event"')
    stats["smoke_wall_s"] = round(time.perf_counter() - t0, 2)
    return stats, recs


def child_serve(a) -> None:
    """Requests arrive staggered, so waves join mid-sweep; the served
    generations must be the KV-decode phase's (``_compare_generations``)."""
    jax, devs = _child_setup(a)
    compiles = _Compiles()
    # The serve demo frontend reads its own copy of the pickle: it writes
    # the updated-suffix pickle next to it.
    shutil.copy(
        os.path.join(a.work, "prompts.pkl"), os.path.join(a.work, "serve_prompts.pkl")
    )
    stats, recs = _serve(
        a, "scores_serve.pkl", ("--max_new_tokens", str(GEN_TOKENS)),
        [
            ("serve.engine", "_prefill_decoders"),
            ("serve.engine", "_suffix_prefill_decoders"),
            ("serve.engine", "_decode_decoders"),
        ],
    )
    n = len(_load(os.path.join(a.work, "prompts.pkl")))
    got = _load(os.path.join(a.work, "scores_serve.pkl"))
    want = _load(os.path.join(a.work, "scores_decode.pkl"))
    tokens = _compare_generations(got, want, "served vs offline KV decode")
    ttft = stats.get("ttft_s", {})
    ok = (
        stats.get("admitted") == n
        and stats.get("completed") == n
        and math.ceil(n / 2) <= stats.get("prefills", 0) <= n
        and stats.get("sweeps", 0) > stats.get("prefills", 0)
        and ttft.get("count") == n
        and ttft.get("mean", 0) > 0
    )
    if not ok:
        raise SystemExit(f"serve stats line fails its contract: {stats}")
    from flexible_llm_sharding_tpu.config import FrameworkConfig

    use_pallas = FrameworkConfig(model_path=a.native_dir).pallas_enabled()
    proof = _kernel_proof(recs, use_pallas, devs[0].platform)
    emit(
        phase="serve", vs_offline_decode=tokens, kernel_proof=proof,
        stats={
            k: stats.get(k)
            for k in (
                "admitted", "completed", "prefills", "sweeps", "tokens_emitted",
                "ttft_s", "token_latency_s", "smoke_wall_s",
            )
        },
        **compiles.report(),
    )


def _per_device_peaks(jax) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.local_devices()]


def child_mode(a) -> None:
    """One four-chip scoring mode vs the one-chip run, with a per-device
    check that every chip held weights and ran work (allocator peak)."""
    jax, devs = _child_setup(a)
    extra = {
        "mp": (),
        "dp": ("--data_parallel", "true"),
        "tp": ("--tensor_parallel", str(a.chips)),
    }[a.mode]
    stats = _run_cli(_score_argv(a, f"scores_{a.mode}.pkl", extra=extra))
    cmp = _compare(
        _load(os.path.join(a.work, f"scores_{a.mode}.pkl")),
        _load(os.path.join(a.work, "scores_cpu.pkl")),
        f"{a.mode} scores vs one chip",
    )
    out = {"phase": "mode", "mode": a.mode, "vs_one_chip": cmp, "stats": stats}
    if devs[0].platform == "tpu":
        peaks = _per_device_peaks(jax)
        out["peak_bytes_per_device"] = peaks
        # One decoder layer is the least a chip that took part must have held.
        layer_bytes = os.path.getsize(
            os.path.join(a.native_dir, "model.layers.0.safetensors")
        )
        floor = layer_bytes // a.chips if a.mode == "tp" else layer_bytes
        if min(peaks) < floor:
            raise SystemExit(
                f"{a.mode}: a chip peaked under {floor} bytes — it held no "
                f"weights: {peaks}"
            )
    emit(**out)


def child_fleet(a) -> None:
    """``serve --replicas N``: which device each replica used, and the first
    served token against the one-chip scoring distribution."""
    jax, devs = _child_setup(a)
    shutil.copy(
        os.path.join(a.work, "prompts.pkl"), os.path.join(a.work, "serve_prompts.pkl")
    )
    stats, _ = _serve(
        a, "scores_fleet.pkl",
        ("--max_new_tokens", "2", "--replicas", str(a.chips)), [],
    )
    reps = stats["replicas"]
    placement = {idx: r["device"] for idx, r in sorted(reps.items())}
    served = {idx: r.get("completed", 0) for idx, r in sorted(reps.items())}
    if len(set(placement.values())) != a.chips:
        raise SystemExit(f"replicas share devices: {placement}")
    n = len(_load(os.path.join(a.work, "prompts.pkl")))
    if sum(served.values()) != n or min(served.values()) == 0:
        raise SystemExit(f"fleet completed {served} of {n} requests sent")
    cmp = _compare(
        [np.asarray(s)[:, :1] for s in _load(os.path.join(a.work, "scores_fleet.pkl"))],
        _load(os.path.join(a.work, "scores_cpu.pkl")),
        "fleet first token vs one-chip scoring",
    )
    out = dict(
        phase="fleet", replica_devices=placement, completed_per_replica=served,
        first_token_vs_one_chip=cmp, router=stats.get("router"),
    )
    if devs[0].platform == "tpu":
        out["peak_bytes_per_device"] = _per_device_peaks(jax)
    emit(**out)


CHILDREN = {
    "device": child_device,
    "score": child_score,
    "score_alt": child_score_alt,
    "decode": child_decode,
    "serve": child_serve,
    "mode": child_mode,
    "fleet": child_fleet,
}


# ---------------------------------------------------------------------------
# Parent: never imports JAX
# ---------------------------------------------------------------------------

def _run(cmd: list[str], what: str, timeout: float, env=None) -> list[dict]:
    """Run one process to its end (killed at ``timeout``); its stdout JSON
    lines are re-printed and returned. A non-zero exit fails the smoke."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True, timeout=timeout, env=env
    )
    lines = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            obj = json.loads(line)
            obj.pop("ok", None)  # only the parent's last line may say ok
            lines.append(obj)
            print(json.dumps(obj), flush=True)
    if proc.returncode != 0:
        raise SystemExit(f"{what} failed (exit {proc.returncode})")
    emit(phase="process", what=what, wall_s=round(time.perf_counter() - t0, 2))
    return lines


def parent(a) -> None:
    t_all = time.perf_counter()
    if "jax" in sys.modules:
        raise SystemExit("the parent must stay off JAX: children take the chip")
    shutil.rmtree(a.work, ignore_errors=True)
    os.makedirs(a.work)
    a.native_dir = os.path.join(a.work, "native")
    hf_dir = os.path.join(a.work, "hf")

    def child(phase: str, *extra: str, timeout: float = 900.0) -> list[dict]:
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child", phase,
            "--work", a.work, "--chips", str(a.chips), *extra,
        ]
        if a.cpu_rehearsal:
            cmd.append("--cpu-rehearsal")
        return _run(cmd, f"child {phase} {' '.join(extra)}".strip(), timeout)

    # The chip first: no chip, no 6 GB of checkpoint.
    device = child("device", timeout=300.0)[0]["device"]

    model = TOY if a.cpu_rehearsal else LLAMA3_8B
    layers = a.layers or (3 if a.cpu_rehearsal else 8)
    t0 = time.perf_counter()
    total = build_hf_checkpoint(model, layers, a.seed, hf_dir)
    emit(
        phase="build_hf", seed=a.seed, layers=layers, gb=round(total / 1e9, 3),
        wall_s=round(time.perf_counter() - t0, 2), **model,
    )
    _run(
        [sys.executable, os.path.join(ROOT, "prepare_weights.py"), hf_dir,
         a.native_dir, "--dtype", "bfloat16"],
        "prepare_weights.py", 900.0,
    )
    shutil.rmtree(hf_dir)
    _run(
        [sys.executable, os.path.join(ROOT, "main.py"), "verify",
         "--model_path", a.native_dir],
        "main.py verify", 600.0,
    )

    prompts = make_prompts(
        n=8, prefix_words=40 if a.cpu_rehearsal else 300, suffix_words=24,
        n_suffix=4,
    )
    with open(os.path.join(a.work, "prompts.pkl"), "wb") as f:
        pickle.dump(prompts, f)

    child("score")
    if a.chips == 1:
        child("score_alt")
        child("decode")
        child("serve")
    else:
        for mode in ("mp", "dp", "tp"):
            child("mode", "--mode", mode)
        child("fleet")

    if not a.keep:
        shutil.rmtree(a.work, ignore_errors=True)
    emit(phase="total", wall_s=round(time.perf_counter() - t_all, 2))
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--layers", type=int, default=0,
                   help="decoder layers (default 8; 3 in --cpu-rehearsal)")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="toy-size run on the CPU backend (JAX_PLATFORMS=cpu); "
                        "reports platform cpu and proves nothing about a chip")
    p.add_argument("--keep", action="store_true", help="keep chip_smoke_tmp/")
    p.add_argument("--work", default=WORK, help=argparse.SUPPRESS)
    p.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    p.add_argument("--mode", choices=("mp", "dp", "tp"), help=argparse.SUPPRESS)
    a = p.parse_args()
    if a.child:
        a.native_dir = os.path.join(a.work, "native")
        CHILDREN[a.child](a)
    else:
        parent(a)


if __name__ == "__main__":
    main()
