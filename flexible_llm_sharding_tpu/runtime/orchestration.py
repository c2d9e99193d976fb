"""Multi-device orchestration: fan prompts/stages out over the chips.

Reference equivalent: the thread-per-CUDA-device fan-out
(``/root/reference/main.py:14-25,59-76``). Here the devices are the chips of
one TPU slice (``jax.devices()``); DP fans a prompt split out to per-device
streaming executors, exactly the reference's ``np.array_split`` semantics.
Threads carry only host-side work (file reads, dispatch) — device compute is
async under XLA, so the threads overlap naturally without a GIL fight.
"""

from __future__ import annotations

from concurrent.futures import FIRST_EXCEPTION, ThreadPoolExecutor, wait
from typing import Sequence

import jax
import numpy as np

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.faults.inject import FaultInjector
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.parallel.planner import (
    batch_ranges,
    plan_shards_dp,
    split_prompts_dp,
)
from flexible_llm_sharding_tpu.runtime import hostcache, residency
from flexible_llm_sharding_tpu.runtime.executor import (
    BroadcastShardSource,
    SourceClosed,
    StreamingExecutor,
    SweepClock,
    np_dtype_for,
)
from flexible_llm_sharding_tpu.runtime.generation import Prompt
from flexible_llm_sharding_tpu.utils import checkpoint


# Per-rank stats ACCUMULATED across every DP run_prompts fan-out since the
# last clear: {rank: {prompts, total_wall_s, compute_wall_s,
# source_wait_s}}. Multi-pass runs (generation_loop calls run_prompts once
# per generated token) sum into the same ranks, so the decomposition covers
# the whole run. The CLI clears it at run start and attaches it to the
# final stats line, showing WHERE each rank's wall went (broadcast-queue
# starvation vs compute). Library callers mixing DP and non-DP runs in one
# process should clear between runs.
LAST_DP_RANK_STATS: dict[int, dict[str, float]] = {}


def pick_devices(cfg: FrameworkConfig) -> list:
    # local_devices, not devices: the streaming executors device_put host
    # arrays, which only works on THIS process's addressable chips. On a
    # multi-host cluster each process runs its own prompt slice over its own
    # chips (cli.py shards by process_index); jax.devices() would hand us
    # remote, non-addressable devices and fail at the first transfer.
    devs = jax.local_devices()
    if cfg.num_devices > 0:
        devs = devs[: cfg.num_devices]
    return devs


def _gather_dp(pool: ThreadPoolExecutor, futures, source) -> list:
    """Collect DP worker results without the consumer-crash deadlock: if a
    worker dies it stops draining its broadcast queue, the producer blocks on
    that full queue, and every OTHER rank starves — so on the first failure
    the source is closed (unblocking all queues) BEFORE gathering, and the
    root-cause exception is re-raised in preference to the secondary
    SourceClosed errors the surviving workers die with."""
    try:
        done, _ = wait(futures, return_when=FIRST_EXCEPTION)
        if any(f.exception() is not None for f in done):
            source.close()
            wait(futures)
            root = None
            for f in futures:
                e = f.exception()
                if e is not None and (root is None or isinstance(root, SourceClosed)):
                    root = e
            raise root
        return [f.result() for f in futures]
    finally:
        source.close()
        pool.shutdown(wait=True)


_probe_chip = residency.probe_chip


def _run_batched(
    ex: StreamingExecutor, prompts: list[Prompt], num_batch: int,
    clock: SweepClock | None = None,
):
    """The reference's num_batch loop (``/root/reference/main.py:19-23``):
    each batch is a full streaming pass (bounds activation-store footprint).
    The batch index scopes disk activation files/markers so crash resume of
    one batch can't be clobbered by another's re-run. ``clock``: an account
    already running for the first pass; later passes open their own."""
    out: list[np.ndarray] = []
    for i, (lo, hi) in enumerate(batch_ranges(len(prompts), num_batch)):
        out += ex(prompts[lo:hi], batch=i, clock=clock if i == 0 else None)
    return out


def _run_single(
    cfg: FrameworkConfig, device, prompts: list[Prompt], tokenizer
) -> list[np.ndarray]:
    """One executor on one placement target. The first sweep's account
    opens here, before the executor exists, so that its head holds the
    executor's construction: every call builds its own."""
    with SweepClock() as clock:
        with obs_trace.span(
            "executor_init", cat="sweep", sweep_id=clock.sweep_id
        ):
            ex = StreamingExecutor(cfg, device=device, tokenizer=tokenizer)
        return _run_batched(ex, prompts, cfg.num_batch, clock)


def _long_context_split(cfg: FrameworkConfig, prompts, tokenizer):
    """The long-context routing predicate, shared by the scoring and decode
    entry points: returns (tokenizer, long_idx, rest_idx) — indices of
    prompts whose prefix overflows one chip's cap (routed to the sp mesh;
    the reference truncates them, ``/root/reference/utils.py:250,254``)."""
    from flexible_llm_sharding_tpu.runtime.longcontext import prefix_token_count

    if tokenizer is None:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
    long_idx = [
        i
        for i, (p, _) in enumerate(prompts)
        if prefix_token_count(tokenizer, p) > cfg.max_token_len
    ]
    long_set = set(long_idx)
    rest_idx = [i for i in range(len(prompts)) if i not in long_set]
    return tokenizer, long_idx, rest_idx


def _merge_by_index(n: int, *parts) -> list:
    """parts: (idx_list, values) pairs -> one list in original prompt order."""
    out: list = [None] * n
    for idxs, vals in parts:
        for i, v in zip(idxs, vals):
            out[i] = v
    return out


def _tp_placement(cfg: FrameworkConfig, devices: list):
    """Build the Megatron placement for --tensor_parallel (shared by the
    scoring and decode entry points)."""
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    if len(devices) < cfg.tensor_parallel:
        raise ValueError(
            f"tensor_parallel={cfg.tensor_parallel} needs that many "
            f"chips, have {len(devices)}"
        )
    model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
    placement = TpPlacement(devices[: cfg.tensor_parallel], model_cfg)
    placement.check(model_cfg)
    return placement


def _dp_targets(cfg: FrameworkConfig, devices: list, model_cfg):
    """Execution targets for the DP prompt split: the chips themselves, or —
    with ``tensor_parallel > 1`` (dp x tp composition) — one ``TpPlacement``
    per group of tp chips."""
    tp = cfg.tensor_parallel
    if tp <= 1:
        return list(devices), len(devices)
    n = len(devices) // tp
    if n < 2:
        raise ValueError(
            f"data_parallel with tensor_parallel={tp} needs at least "
            f"{2 * tp} chips (2+ groups of tp), have {len(devices)}; drop "
            "--data_parallel for single-group tensor parallelism"
        )
    if len(devices) % tp:
        import sys

        print(
            f"dp x tp: {len(devices) % tp} of {len(devices)} chips idle "
            f"(device count not a multiple of tensor_parallel={tp})",
            file=sys.stderr,
        )
    from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

    targets = [
        TpPlacement(devices[g * tp : (g + 1) * tp], model_cfg) for g in range(n)
    ]
    targets[0].check(model_cfg)  # same config for every group: check once
    return targets, n


def run_prompts(
    cfg: FrameworkConfig,
    prompts: Sequence[Prompt],
    tokenizer=None,
    devices: list | None = None,
) -> list[np.ndarray]:
    """Score all prompts once over the available devices -> one
    ``[n_suffixes, 1, vocab]`` array per prompt, in prompt order."""
    prompts = list(prompts)
    if not prompts:
        return []
    devices = devices if devices is not None else pick_devices(cfg)

    if cfg.long_context:
        # Prompts whose prefix overflows one chip's bucket are scored
        # exactly over an sp mesh (ring attention); the rest take the
        # normal streaming path.
        from flexible_llm_sharding_tpu.runtime.longcontext import (
            LongContextScorer,
        )

        tokenizer, long_idx, rest_idx = _long_context_split(
            cfg, prompts, tokenizer
        )
        if long_idx:
            import dataclasses

            scorer = LongContextScorer(cfg, devices=devices, tokenizer=tokenizer)
            long_scores = scorer([prompts[i] for i in long_idx])
            rest_scores = (
                run_prompts(
                    dataclasses.replace(cfg, long_context=False),
                    [prompts[i] for i in rest_idx],
                    tokenizer=tokenizer,
                    devices=devices,
                )
                if rest_idx
                else []
            )
            return _merge_by_index(
                len(prompts), (long_idx, long_scores), (rest_idx, rest_scores)
            )

    if cfg.tensor_parallel > 1 and not cfg.data_parallel:
        # One streaming executor whose every shard is Megatron-sharded over a
        # tp mesh: per-chip weight HBM divides by tp, matmuls run on all
        # chips' MXUs, XLA emits the ICI all-reduces. The reference has no
        # equivalent — its layers always live whole on one device
        # (/root/reference/utils.py:128-130).
        return _run_single(
            cfg, _tp_placement(cfg, devices), prompts, tokenizer
        )

    # dp x tp must NOT degrade to the single-device/pipeline branches on a
    # short device list — _dp_targets fails loudly instead (an unsharded
    # stream of a model that needed tp to fit HBM would OOM or mislead).
    dp_tp = cfg.tensor_parallel > 1 and cfg.data_parallel
    if not dp_tp and (len(devices) <= 1 or not cfg.data_parallel):
        if len(devices) > 1:
            from flexible_llm_sharding_tpu.runtime.pipeline import run_pipeline

            return run_pipeline(cfg, prompts, devices, tokenizer=tokenizer)
        return _run_single(cfg, devices[0], prompts, tokenizer)

    # DP: prompt ranges per execution target (np.array_split semantics,
    # /root/reference/main.py:70), one streaming executor per target. All
    # targets stream the same shards in lockstep, so the checkpoint is read
    # from disk ONCE per shard and broadcast (BroadcastShardSource) — the
    # TPU-native replacement for the reference's DeviceManager layer cache
    # (/root/reference/utils.py:31-75). Targets whose prompt range is empty
    # (more targets than prompts) are excluded from the broadcast entirely,
    # so the producer never waits on an idle queue. With tensor_parallel > 1
    # the targets are GROUPS of tp chips (dp x tp composition): each group
    # streams Megatron-sharded weights over its own sub-mesh — _place
    # broadcasts the int8/bf16 host shard once per group placement.
    model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
    targets, n = _dp_targets(cfg, devices, model_cfg)
    ranges = split_prompts_dp(len(prompts), n)
    layer_names = checkpoint.layer_names_for(
        model_cfg.num_hidden_layers, tie_word_embeddings=False
    )
    n_exec_layers = len(layer_names)
    plan = plan_shards_dp(
        n_exec_layers, cfg.layer_num_per_shard,
        loop_steps=model_cfg.total_ut_steps,
    )
    active = [rank for rank in range(n) if ranges[rank][0] < ranges[rank][1]]
    source = BroadcastShardSource(
        cfg.model_path,
        layer_names,
        plan.shards,
        np_dtype_for(cfg.dtype),
        devices=[targets[r] for r in active],
        prefetch_depth=cfg.effective_prefetch_depth(),
        tied_embeddings=model_cfg.tie_word_embeddings,
        rounds=cfg.num_batch,
        residency=residency.tier_for(
            cfg, layer_names, model_cfg.tie_word_embeddings,
            # active is non-empty here (run_prompts early-returns on
            # empty prompts); the fallback keeps an all-inactive split
            # from a future caller at a rank-0 probe, not an IndexError.
            _probe_chip(targets[active[0]] if active else targets[0]),
        ),
        layer_sliding=model_cfg.layer_sliding,
        layer_rope=model_cfg.layer_rope,
        layer_linear=model_cfg.layer_linear,
        retry_policy=cfg.retry_policy(),
        injector=FaultInjector.from_config(cfg.faults),
        verify_weights=cfg.verify_weights,
        host_cache=hostcache.cache_for(cfg),
        readahead_threads=cfg.readahead_threads,
    )

    def run_one(slot: int) -> list[np.ndarray]:
        rank = active[slot]
        lo, hi = ranges[rank]
        ex = StreamingExecutor(
            cfg,
            device=targets[rank],
            plan=plan_shards_dp(
                n_exec_layers,
                cfg.layer_num_per_shard,
                device_rank=rank,
                num_devices=n,
                loop_steps=model_cfg.total_ut_steps,
            ),
            tokenizer=tokenizer,
            weight_source_factory=lambda: source.view(slot),
        )
        try:
            return _run_batched(ex, prompts[lo:hi], cfg.num_batch)
        finally:
            # Per-rank wall/wait/compute decomposition for the run's stats
            # line: distinguishes "ranks starved on the shared broadcast
            # queue" (source_wait dominates) from "ranks compute-bound"
            # (e.g. N virtual devices oversubscribing one CPU core).
            agg = LAST_DP_RANK_STATS.setdefault(
                rank, {"prompts": float(hi - lo)}
            )
            for call in ex.stats_history:
                for key in (
                    "total_wall_s", "compute_wall_s", "source_wait_s"
                ):
                    if key in call:
                        agg[key] = agg.get(key, 0.0) + call[key]

    pool = ThreadPoolExecutor(max_workers=len(active))
    futures = [pool.submit(run_one, slot) for slot in range(len(active))]
    outputs = _gather_dp(pool, futures, source)
    return [s for chunk in outputs for s in chunk]


def run_decode(
    cfg: FrameworkConfig,
    prompts: Sequence[Prompt],
    tokenizer=None,
    devices: list | None = None,
):
    """KV-cache decode over the available devices.

    Single chip: one DecodeGenerator. Multiple chips: DP prompt split
    (array_split, reference ``/root/reference/main.py:70``) with ONE shared
    BroadcastShardSource reading the checkpoint once per weight stream —
    prefill plus each decode step, ``rounds=num_gen_token`` total.

    Returns (scores, updated_prompts, tokens_processed).
    """
    from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

    prompts = list(prompts)
    if not prompts:
        return [], [], 0
    devices = devices if devices is not None else pick_devices(cfg)

    if cfg.long_context:
        # Prompts whose prefix overflows one chip's bucket decode over the
        # sp mesh with sharded prefix KV (the reference would truncate them
        # AND re-run the full prompt per token); the rest take the normal
        # KV-decode paths below.
        from flexible_llm_sharding_tpu.runtime.longcontext import (
            LongContextDecoder,
        )

        tokenizer, long_idx, rest_idx = _long_context_split(
            cfg, prompts, tokenizer
        )
        if long_idx:
            import dataclasses

            dec = LongContextDecoder(cfg, devices=devices, tokenizer=tokenizer)
            l_scores, l_updated, l_tokens = dec([prompts[i] for i in long_idx])
            if rest_idx:
                r_scores, r_updated, r_tokens = run_decode(
                    dataclasses.replace(cfg, long_context=False),
                    [prompts[i] for i in rest_idx],
                    tokenizer=tokenizer,
                    devices=devices,
                )
            else:
                r_scores, r_updated, r_tokens = [], [], 0
            return (
                _merge_by_index(
                    len(prompts), (long_idx, l_scores), (rest_idx, r_scores)
                ),
                _merge_by_index(
                    len(prompts), (long_idx, l_updated), (rest_idx, r_updated)
                ),
                l_tokens + r_tokens,
            )

    if cfg.tensor_parallel > 1 and not cfg.data_parallel:
        # TP decode: one generator whose streamed weights are Megatron-
        # sharded over the tp mesh; activations and parked KV stay
        # replicated (weights are the HBM/transfer term the split targets).
        gen = DecodeGenerator(
            cfg, device=_tp_placement(cfg, devices), tokenizer=tokenizer
        )
        scores, updated = gen(prompts)
        return scores, updated, int(gen.stats.get("tokens_processed", 0))

    if len(devices) > 1 and not cfg.data_parallel:
        # Interleaved-pipeline decode (reference MP assignment): each
        # stage's weights and parked KV live on its own chip, activations
        # hop over ICI; one driver, no prompt split needed.
        gen = DecodeGenerator(cfg, tokenizer=tokenizer, mp_devices=devices)
        scores, updated = gen(prompts)
        return scores, updated, int(gen.stats.get("tokens_processed", 0))

    dp_tp = cfg.tensor_parallel > 1 and cfg.data_parallel
    if not dp_tp and (len(devices) <= 1 or len(prompts) <= 1):
        gen = DecodeGenerator(
            cfg, device=devices[0] if devices else None, tokenizer=tokenizer
        )
        scores, updated = gen(prompts)
        return scores, updated, int(gen.stats.get("tokens_processed", 0))

    # DP decode (with tensor_parallel > 1: dp x tp — one TpPlacement per
    # group of tp chips, Megatron-sharded weights broadcast once per group).
    model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
    targets, n = _dp_targets(cfg, devices, model_cfg)
    ranges = split_prompts_dp(len(prompts), n)
    layer_names = checkpoint.layer_names_for(
        model_cfg.num_hidden_layers, tie_word_embeddings=False
    )
    plan = plan_shards_dp(len(layer_names), cfg.layer_num_per_shard)
    active = [rank for rank in range(n) if ranges[rank][0] < ranges[rank][1]]
    # Weights-resident decode: one broadcast round (the prefill) instead of
    # one per generated token — every rank keeps its placed shards on chip.
    # Decided HERE so the shared source's round count and every generator's
    # behaviour agree (a rank deciding differently would starve/overflow
    # the broadcast queues).
    t0 = targets[active[0]]
    resident = cfg.decode_resident_enabled(
        model_cfg,
        t0.mesh.devices.size if hasattr(t0, "segment_target") else 1,
        _probe_chip(t0),
    )
    source = BroadcastShardSource(
        cfg.model_path,
        layer_names,
        plan.shards,
        np_dtype_for(cfg.dtype),
        devices=[targets[r] for r in active],
        prefetch_depth=cfg.effective_prefetch_depth(),
        tied_embeddings=model_cfg.tie_word_embeddings,
        rounds=1 if resident else cfg.num_gen_token,
        # Residency is moot once the decode is fully resident (one
        # broadcast round, shards kept on chip); in the streaming regime
        # every per-token round skips the pinned layers' bytes.
        residency=(
            None
            if resident
            else residency.tier_for(
                cfg, layer_names, model_cfg.tie_word_embeddings,
                _probe_chip(targets[active[0]]),
            )
        ),
        layer_sliding=model_cfg.layer_sliding,
        layer_rope=model_cfg.layer_rope,
        retry_policy=cfg.retry_policy(),
        injector=FaultInjector.from_config(cfg.faults),
        verify_weights=cfg.verify_weights,
        host_cache=hostcache.cache_for(cfg),
        readahead_threads=cfg.readahead_threads,
    )

    def run_one(slot: int):
        rank = active[slot]
        lo, hi = ranges[rank]
        gen = DecodeGenerator(
            cfg,
            device=targets[rank],
            tokenizer=tokenizer,
            weight_source_factory=lambda: source.view(slot),
            resident=resident,
        )
        scores, updated = gen(prompts[lo:hi])
        return scores, updated, int(gen.stats.get("tokens_processed", 0))

    pool = ThreadPoolExecutor(max_workers=len(active))
    futures = [pool.submit(run_one, slot) for slot in range(len(active))]
    outputs = _gather_dp(pool, futures, source)
    scores = [s for (sc, _, _) in outputs for s in sc]
    updated = [u for (_, up, _) in outputs for u in up]
    tokens = sum(t for (_, _, t) in outputs)
    return scores, updated, tokens


__all__ = ["run_prompts", "run_decode", "pick_devices"]
