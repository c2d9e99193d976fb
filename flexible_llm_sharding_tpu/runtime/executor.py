"""The streaming sharded executor — the framework's core.

Reference equivalent: ``ShardedLlama.__call__`` (``/root/reference/utils.py:133-305``),
which streams a Llama through one device layer-by-layer: load a shard of
layers, run *all* prompts through it, stash activations, evict, next shard.

TPU-first redesign (SURVEY.md §7):

- Layers are pure functions over parameter pytrees; "loading a shard" is one
  host->HBM ``jax.device_put`` of a stacked pytree, "evicting" is dropping the
  reference (XLA's allocator reuses the buffer — no ``malloc_trim``/reboot
  dance, cf. ``/root/reference/utils.py:18-21,134-137``).
- A shard of k decoder layers runs as ONE jitted program: ``lax.scan`` over
  the stacked [k, ...] parameter pytree, vmapped over a block of same-bucket
  prompts. One compile per (bucket-shape, k) family serves all layers and all
  shards — the reference pays a per-layer Python/dispatch cost instead.
- Shapes are static (bucketed); true prefix lengths / eos indices are dynamic
  values folded into masks and gathers, so there is no per-prompt retracing.
- Weight upload can be overlapped with compute via a prefetch thread
  (``prefetch_depth >= 1``), replacing the reference's fully serialized
  load-then-compute loop (``/root/reference/utils.py:228-233`` — its #1
  inefficiency).
"""

from __future__ import annotations

import contextlib
import functools
import gc
import itertools
import logging
import os
import threading
import time
from collections import deque
from functools import partial
from queue import Empty, Full, Queue, SimpleQueue
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.faults.inject import FaultInjector
from flexible_llm_sharding_tpu.faults.retry import (
    RetryPolicy,
    ShardLoadError,
    retry_call,
)
from flexible_llm_sharding_tpu.integrity import manifest as integrity_manifest
from flexible_llm_sharding_tpu.integrity.manifest import (
    ChecksumMismatch,
    ShardCorruptError,
    SpillCorruptError,
)
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.obs import events as obs_events
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.obs.registry import REGISTRY as _OBS_REGISTRY
from flexible_llm_sharding_tpu.obs.registry import describe as _describe_gauges
from flexible_llm_sharding_tpu.ops import pallas_attention
from flexible_llm_sharding_tpu.parallel.planner import (
    ShardPlan,
    ShardVisit,
    decoder_visits,
    plan_shards_dp,
    visit_order,
)
from flexible_llm_sharding_tpu.runtime.activations import ActivationStore
from flexible_llm_sharding_tpu.runtime.pressure import (
    HostOOMError,
    note_event as _note_pressure_event,
)
from flexible_llm_sharding_tpu.runtime.tokenization import (
    PromptTokenizer,
    check_dense_len,
    check_longrope_regime,
    longrope_total_len,
    TokenizedPrompt,
    make_blocks,
)
from flexible_llm_sharding_tpu.runtime import resume
from flexible_llm_sharding_tpu.utils import checkpoint, metrics
from flexible_llm_sharding_tpu.utils.intervals import idle_split, union_seconds

Params = dict[str, Any]

_DTYPES = {
    "float32": jnp.float32,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
}


def np_dtype_for(dtype_name: str) -> np.dtype:
    """Host-side numpy dtype for a FrameworkConfig.dtype string (bfloat16
    resolves to the ml_dtypes extension type)."""
    return np.dtype(jnp.dtype(_DTYPES[dtype_name]).name)


# ---------------------------------------------------------------------------
# Jitted stage programs (module-level so the jit cache is shared across
# executors; cfg is a frozen dataclass -> hashable -> static arg)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 1))
def _embed_block(cfg: LlamaConfig, dtype, embed_params, prefix_ids, suffix_ids):
    """ids [B, Lp], [B, S, Ls] -> hidden [B, Lp, D], [B, S, Ls, D]."""
    # Each jitted step's body runs under a scope of its own name, so the
    # device's ops carry the step in their op_name whatever jit they ran in.
    with jax.named_scope("embed"):
        return (
            llama.embed(embed_params, prefix_ids, dtype, cfg),
            llama.embed(embed_params, suffix_ids, dtype, cfg),
        )


@partial(jax.jit, static_argnums=(0, 5, 6, 8), donate_argnums=(2, 3))
def _decoder_block(
    cfg: LlamaConfig, seg, prefix_h, suffix_h, prefix_len, use_pallas=False,
    tp_mesh=None, total_len=None, moe_stats=False,
):
    """Scan k stacked decoder layers over a block of prompts.

    seg: {"layers": pytree with leading [k] axis, "sliding": bool [k] per-
    layer local-attention flags or None (uniform), "rope": bool [k]
    per-layer rope flags or None, "index": int32 [k], the layers' indices
    among the model's decoder layers, for a model with linear-attention
    layers or a per-layer table (absent otherwise: what a layer's decay and
    clamps follow, ``llama.layer_log_decay`` / ``layer_swiglu_limits``)};
    prefix_h [B, Lp, D]; suffix_h [B, S, Ls, D]; prefix_len int32 [B].
    Activations are donated — each scan step's output reuses the input
    buffers. ``use_pallas`` (static) routes
    attention through the flash kernels; ``tp_mesh`` (static, hashable)
    makes them run per head-shard via shard_map under tensor parallelism.
    ``total_len`` int32 [B] (longrope only): per-prompt real total length
    for the long/short rope table choice. ``moe_stats`` (static): a third
    output, int32 [4], the block's (assignments on held experts, all
    assignments, rows of the grouped matmuls' row tiles, held experts given a
    row) summed over its layers and prompts (an expert layer that
    holds a share of its experts: ``llama._deepseek_moe_mlp``).

    A layer's attention half runs per prompt under ``vmap``; its MLP half is
    position-wise and runs once over the block's B*Lp + B*S*Ls rows, outside
    the ``vmap``, so that a sigmoid-router expert layer can sort the block's
    rows by expert and compute each in its chosen experts only
    (``llama._routed_experts``; under ``use_pallas`` its grouped matmuls are
    the Pallas kernel where eligible). Under ``tp_mesh`` the stacked experts are
    sharded on their leading axis and the layer keeps the compute-all
    einsums that GSPMD partitions (``parallel/sharding.py``).
    """
    stacked, flags = seg["layers"], seg["sliding"]
    rflags = seg.get("rope")
    # A float leaf of the segment would go through the placement's cast to
    # the compute dtype: the decays are float32 constants of the program,
    # picked by the layers' indices.
    decay = llama.layer_log_decay(cfg)
    if decay is not None:
        decay = jnp.asarray(decay)[seg["index"]]
    # A model that clamps its SwiGLUs: each layer's row, as the decays.
    limits = llama.layer_swiglu_limits(cfg)
    if limits is not None:
        limits = jnp.asarray(limits)[seg["index"]]

    def body(carry, xs):
        layer_params, sliding, rope_on, log_decay, limit = xs
        p, s, counts = carry

        def attention_half(lp_, c_, p_, s_, plen_, tlen_):
            return llama.prefix_suffix_layer(
                lp_, c_, p_, s_, plen_,
                use_pallas=use_pallas,
                sliding=sliding,
                rope_on=rope_on,
                tp_mesh=tp_mesh,
                total_len=tlen_,
                attn_only=True,
                log_decay=log_decay,
            )

        step = jax.vmap(
            attention_half,
            in_axes=(None, None, 0, 0, 0, 0 if total_len is not None else None),
        )
        stats, tiles = ([], []) if moe_stats else (None, None)
        with jax.named_scope("decoder_layer"):
            p, s = step(layer_params, cfg, p, s, prefix_len, total_len)
            d = p.shape[-1]
            rows = jnp.concatenate([p.reshape(-1, d), s.reshape(-1, d)])
            rows = llama._residual_mlp(
                layer_params, cfg, rows, stats,
                grouped=tp_mesh is None, use_pallas=use_pallas, limits=limit,
                tiles=tiles,
            )
            p, s = (
                rows[: p.size // d].reshape(p.shape),
                rows[p.size // d :].reshape(s.shape),
            )
        if moe_stats:
            counts = counts + jnp.concatenate(
                [llama._moe_counts(stats), llama._moe_counts(tiles)]
            )
        return (p, s, counts), None

    # flags may be None: scan treats them as empty subtrees, and the body's
    # sliding/rope args arrive as None (the static uniform paths).
    (prefix_h, suffix_h, counts), _ = jax.lax.scan(
        body,
        (prefix_h, suffix_h, jnp.zeros((4,), jnp.int32) if moe_stats else None),
        (stacked, flags, rflags, decay, limits),
    )
    if moe_stats:
        return prefix_h, suffix_h, counts
    return prefix_h, suffix_h


@partial(jax.jit, static_argnums=(0,))
def _norm_block(cfg: LlamaConfig, norm_params, suffix_h, suffix_eos):
    """[B, S, Ls, D], eos [B, S] -> last-token normed [B, S, 1, D]
    (``/root/reference/utils.py:281-286``)."""
    with jax.named_scope("final_norm"):
        return jax.vmap(llama.select_eos_and_norm, in_axes=(None, None, 0, 0))(
            norm_params, cfg, suffix_h, suffix_eos
        )


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
def _loop_norm_block(
    cfg: LlamaConfig, norm_params, prefix_h, suffix_h, suffix_eos, exit_state, step
):
    """A looped model's step end before the last: the final norm over EVERY
    prefix and suffix row (its output feeds the next step; donated like a
    decoder step's activations) and the exit gate on the scored rows (each
    suffix's last real token). ``step`` int32 scalar, 1-based and traced:
    one program serves every step. ``exit_state``: ``llama.exit_init``'s
    tuple over [B, S]. -> (prefix_h, suffix_h, exit_state)."""
    with jax.named_scope("loop_norm"):
        prefix_h = llama.final_norm(norm_params, cfg, prefix_h)
        suffix_h = llama.final_norm(norm_params, cfg, suffix_h)
    scored = jnp.take_along_axis(suffix_h, suffix_eos[:, :, None, None], axis=2)
    return prefix_h, suffix_h, llama.exit_step(
        cfg, norm_params, exit_state, scored, step, last=False
    )


_exit_init = jax.jit(llama.exit_init, static_argnums=(0, 1, 2))


@partial(jax.jit, static_argnums=(0,))
def _exit_block(cfg: LlamaConfig, norm_params, suffix_h, exit_state, real):
    """A looped model's last step end, after ``_norm_block``: suffix_h
    [B, S, 1, D] is the last step's normed output of the scored rows. ->
    (what the head reads: each row's state at the step the exit rule gave
    it, the last step's at a threshold >= 1; the gate's expected exit step
    summed over the ``real`` [B, S] rows, float32 scalar)."""
    _, _, expected, chosen = llama.exit_step(
        cfg, norm_params, exit_state, suffix_h, cfg.total_ut_steps, last=True
    )
    out = suffix_h if cfg.early_exit_threshold >= 1 else chosen
    return out, jnp.sum(jnp.where(real, expected, 0.0))


@partial(jax.jit, static_argnums=(0,))
def _head_block(cfg: LlamaConfig, head_params, suffix_h):
    """[B, S, 1, D] -> float32 scores [B, S, V] (``/root/reference/utils.py:287-290``);
    applies Gemma2's final-logit softcap when the config carries one."""
    with jax.named_scope("lm_head"):
        return jax.vmap(
            partial(llama.lm_head_scores, softcap=cfg.final_logit_softcap),
            in_axes=(None, 0),
        )(head_params, suffix_h)


def process_block(
    model_cfg: LlamaConfig,
    dtype,
    segments,
    visit: ShardVisit,
    store,
    b: int,
    idxs,
    meta,
    device,
    toks,
    scores: dict,
    use_pallas: bool = False,
    tp_mesh=None,
    fetched=None,
    clock=None,
):
    """Run one shard over one block: fetch its activations (unless this shard
    is the first visit, the embedding's), apply the segments, scatter any
    head scores, and store activations for the next shard. The per-block
    body shared by the single-device executor and the MP pipeline runner.
    What the shard's place in the order of visits means (nothing fetched at
    the first visit, prefix states dead after the last decoder visit,
    nothing stored after the last visit) is ``visit``'s to say
    (``parallel.planner.ShardVisit``, the one place that states those
    invariants); that score rows truncate to the true suffix count lives
    here. A looped model's exit state rides the store between a block's
    shards (``store.exit_state``).

    ``fetched``: optional (prefix_h, suffix_h) override — already-on-device
    activations that REPLACE the store fetch (the executor's corruption
    recompute path re-derives a block's inputs when its spill failed
    verification, then re-enters here).

    ``clock``: the pass's ``SweepClock`` (or None: the pipeline runner) —
    the store's round trip is charged to it as ``act_fetch`` /
    ``act_store``.

    Returns the block's suffix activations (device array) for optional
    synchronisation by the caller.
    """
    prefix_ids, suffix_ids, prefix_len, suffix_eos = meta
    ids = {} if clock is None else clock.span_ids()
    if visit.embeds:
        prefix_h, suffix_h = None, None  # produced by the embed segment
    elif fetched is not None:
        prefix_h, suffix_h = fetched
        if not visit.needs_prefix:  # norm/head shard: prefix is dead weight
            prefix_h = None
    else:
        with obs_trace.timed("act_fetch", cat="sweep", block=b, **ids) as sp:
            prefix_h, suffix_h = store.fetch(
                b, idxs, with_prefix=visit.needs_prefix
            )
            # Host->HBM upload, or the chip-to-chip ICI hop in pipeline
            # mode. Under TpPlacement activations are replicated over the
            # tp mesh.
            act_target = getattr(device, "act", device)
            suffix_h = jax.device_put(suffix_h, act_target)
            if prefix_h is not None:
                prefix_h = jax.device_put(prefix_h, act_target)
        if clock is not None:
            clock.act_fetch_s += sp.dur_s

    prefix_h, suffix_h, block_scores = apply_segments(
        model_cfg,
        dtype,
        segments,
        prefix_h,
        suffix_h,
        prefix_ids,
        suffix_ids,
        prefix_len,
        suffix_eos,
        use_pallas,
        tp_mesh,
        clock=clock,
        loop=LoopPlace.of(model_cfg, visit, store.exit_state, b, idxs, toks),
    )
    if block_scores is not None:
        for row, i in enumerate(idxs):
            s_true = toks[i].num_suffixes
            # Device-resident [s_true, 1, V] slice; the host copy starts now
            # (async DMA) and is resolved by finalize_scores at run end.
            row_scores = block_scores[row, :s_true, None, :]
            row_scores.copy_to_host_async()
            scores[i] = row_scores
    if visit.stores:
        with obs_trace.timed("act_store", cat="sweep", block=b, **ids) as sp:
            store.store(b, idxs, prefix_h, suffix_h)
        if clock is not None:
            clock.act_store_s += sp.dur_s
    return suffix_h


class ScoreSink(dict):
    """Per-prompt score collector (prompt_idx -> [S, 1, V]).

    Head-stage slices arrive as device arrays with their host DMA already
    started (copy_to_host_async); keeping them ALL device-resident until run
    end would grow HBM with prompt count, so only the newest ``max_device``
    stay pending — older ones resolve to host numpy (their copy has had
    whole blocks of compute to finish, so the wait is ~free). The driver
    thread stays sync-free in the hot loop either way.
    """

    def __init__(self, max_device: int = 16):
        super().__init__()
        self._pending: list = []
        self.max_device = max_device

    def __setitem__(self, k, v):
        super().__setitem__(k, v)
        if hasattr(v, "copy_to_host_async"):
            self._pending.append(k)
            while len(self._pending) > self.max_device:
                kk = self._pending.pop(0)
                super().__setitem__(kk, np.asarray(jax.device_get(self[kk])))


def finalize_scores(scores: dict) -> None:
    """Resolve the remaining device score slices to host numpy in place —
    the run's final host sync point (replaces a device_get per block)."""
    for i, s in scores.items():
        scores[i] = np.asarray(jax.device_get(s))


def apply_segments(
    model_cfg: LlamaConfig,
    dtype,
    segments,
    prefix_h,
    suffix_h,
    prefix_ids,
    suffix_ids,
    prefix_len,
    suffix_eos,
    use_pallas: bool = False,
    tp_mesh=None,
    clock: "SweepClock | None" = None,
    loop: "LoopPlace | None" = None,
):
    """Run one shard's segments over a block.

    Returns (prefix_h, suffix_h, block_scores) where block_scores is the
    float32 [B, S, V] DEVICE array if this shard contained the lm_head, else
    None — no host sync here: a device_get per block would stall the driver
    thread and serialise pipeline stages; callers convert to numpy once at
    the end of the run. Shared by the single-device executor and the MP
    pipeline runner. ``clock`` (the sweep's account, where one is kept):
    its ``expert_rows`` count what each decoder segment's expert layers are
    dispatched with, from the shapes; its ``moe_counts`` get the segment's
    device-resident int32 [4] expert counts where the model holds a share of
    its experts. Nothing is read from the device here. ``loop`` (a looped
    model only): where the shard stands among the loop's steps and the
    block's exit state; a ``norm`` segment before the last step norms every
    row and leaves the prefix alive, the last one is every model's.
    """
    block_scores = None
    moe_stats = clock is not None and model_cfg.moe_ep_size > 1
    # longrope: per-prompt real total length (prefix + longest suffix)
    # selects the long/short rope table; tokenization has already rejected
    # prompts whose suffixes straddle the boundary (check_longrope_regime).
    total_len = longrope_total_len(model_cfg, prefix_len, suffix_eos)
    for kind, params in segments:
        if kind == "embed":
            prefix_h, suffix_h = _embed_block(
                model_cfg, dtype, params, prefix_ids, suffix_ids
            )
        elif kind == "decoders":
            if clock is not None:
                body, n = _expert_rows(
                    params, (prefix_h.size + suffix_h.size) // model_cfg.hc_mult,
                    tp_mesh,
                )
                clock.expert_rows[body] += n
                body, n, state = _linear_rows(
                    model_cfg, params, prefix_h.shape, suffix_h.shape,
                    use_pallas, tp_mesh,
                )
                if model_cfg.linear_kind == "kda":
                    clock.kda_rows[body] += n
                    clock.kda_state_bytes = max(clock.kda_state_bytes, state)
                else:
                    clock.linear_rows[body] += n
                    clock.linear_state_bytes = max(clock.linear_state_bytes, state)
            prefix_h, suffix_h, *counts = _decoder_block(
                model_cfg, params, prefix_h, suffix_h, prefix_len, use_pallas,
                tp_mesh, total_len, moe_stats,
            )
            if moe_stats:
                clock.moe_counts.extend(counts)
        elif kind == "norm" and loop is not None:
            prefix_h, suffix_h = loop.step_end(
                model_cfg, params, prefix_h, suffix_h, suffix_eos, clock
            )
        elif kind == "norm":
            suffix_h = _norm_block(model_cfg, params, suffix_h, suffix_eos)
            prefix_h = None
        else:  # head
            block_scores = _head_block(model_cfg, params, suffix_h)
    return prefix_h, suffix_h, block_scores


class LoopPlace:
    """Where one block stands in a looped model's steps while a shard's
    segments are applied: ``step`` (0-based) counts the final norms the
    block has passed; ``states`` (the activation store's ``exit_state``)
    keeps the block's exit state between shards, as the store keeps its
    activations; ``real``: the true suffix count of each prompt of the
    block (rows past it are padding and count in no mean)."""

    def __init__(self, step: int, states: dict, block: int, real: list[int]):
        self.step, self.states, self.block, self.real = step, states, block, real

    @classmethod
    def of(cls, cfg, visit: ShardVisit, states: dict, block: int, idxs, toks):
        """The block's place at the start of a shard; None for a model
        visited once, whose ``norm`` segment is the plain one."""
        if cfg.total_ut_steps == 1:
            return None
        return cls(visit.step, states, block, [toks[i].num_suffixes for i in idxs])

    def step_end(self, cfg, norm_params, prefix_h, suffix_h, suffix_eos, clock):
        """A ``norm`` segment: the step's end. -> (prefix_h, suffix_h) for
        what follows: every row normed before the last step, the scored
        rows' [B, S, 1, D] and no prefix at the last."""
        self.step += 1
        state = self.states.pop(self.block, None)
        if state is None:
            state = _exit_init(
                suffix_h.shape[:2], suffix_h.shape[-1], suffix_h.dtype
            )
        if self.step < cfg.total_ut_steps:
            prefix_h, suffix_h, self.states[self.block] = _loop_norm_block(
                cfg, norm_params, prefix_h, suffix_h, suffix_eos, state,
                np.int32(self.step),
            )
            return prefix_h, suffix_h
        suffix_h = _norm_block(cfg, norm_params, suffix_h, suffix_eos)
        real = np.arange(suffix_h.shape[1])[None, :] < np.asarray(self.real)[:, None]
        suffix_h, expected = _exit_block(cfg, norm_params, suffix_h, state, real)
        if clock is not None:
            clock.exit_sums.append(expected)
            clock.exit_rows += int(real.sum())
        return None, suffix_h


def _expert_rows(seg, act_size: int, tp_mesh) -> tuple[str, int]:
    """Which body ``_decoder_block`` gives a segment's expert layers, and the
    rows x expert layers it is dispatched with (``act_size``: the elements
    of the block's prefix and suffix activations). ``grouped``: a row is
    computed in its chosen experts only, which a sigmoid-router layer outside
    a ``tp_mesh`` gets; ``dense``: every row in every held expert."""
    mlp = seg["layers"]["mlp"]
    grouped = "correction_bias" in mlp and tp_mesh is None
    layers, d = mlp["router"].shape[:2] if "router" in mlp else (0, 1)  # [k, D, E]
    return ("grouped" if grouped else "dense"), layers * (act_size // d)


def _linear_rows(
    model: LlamaConfig, seg, prefix_shape, suffix_shape, use_pallas: bool, tp_mesh
) -> tuple[str, int, int]:
    """Which body ``_decoder_block`` gives a segment's linear-attention
    layers over a block ([B, Lp, D] and [B, S, Ls, D]), the rows x such
    layers it is dispatched with, and the float32 state its call holds (one
    a prompt); no rows and no state for a segment of softmax layers. A
    layer's kind is read off its weights' shapes, stacked or not."""
    if not llama.is_linear(model, seg["layers"]["attn"]):
        return "xla", 0, 0
    (b, lp, _), (_, s, ls, _) = prefix_shape, suffix_shape
    kernel = llama.linear_uses_kernel(model, lp, ls, use_pallas, tp_mesh)
    heads, _, d, vd = model.attn_shape(linear=True)
    return (
        "kernel" if kernel else "xla",
        seg["index"].shape[0] * b * (lp + s * ls),
        b * heads * d * vd * 4,
    )


def _flash_steps(
    model: LlamaConfig, layer_idxs, n_layers: int, block_shapes, use_pallas: bool,
    tp_mesh,
) -> int:
    """Steps of the online softmax that the two scoring flash kernels run
    when a shard's decoder layers (``layer_idxs``: indices into the
    execution list) pass over a batch's blocks (``block_shapes``: per block
    ``(Lp, S, Ls, the prompts' true prefix lengths)``): every query head's
    loop trips, by the tiles the wrappers pick and the bounds the kernels
    walk (``ops.pallas_attention.causal_steps`` / ``prefix_shared_steps``).
    A host count from the shapes, as ``_expert_rows``; 0 where the layers
    run the XLA ops."""
    return sum(
        _layer_flash_steps(model, i - 1, *block, use_pallas, tp_mesh)
        for i in layer_idxs
        if 0 < i < n_layers - 2
        for block in block_shapes
    )


@functools.lru_cache(maxsize=4096)
def _layer_flash_steps(
    model: LlamaConfig, layer: int, lp: int, s: int, ls: int, prefix_lens,
    use_pallas: bool, tp_mesh,
) -> int:
    """``_flash_steps`` of one decoder layer over one block: a causal call a
    prompt and a prefix-shared call over its ``s`` suffixes (the bucket's:
    padding suffixes run too). Cached: a sweep asks the same few (layer
    kind, block) pairs once a layer visit."""
    plan = llama.layer_attention_plan(model, layer, lp, ls, use_pallas, tp_mesh)
    if plan is None or not plan[0]:
        return 0
    _, (n_q, _, hd, vd), window, chunk, local_on = plan
    return n_q * sum(
        pallas_attention.causal_steps(lp, lp, hd, vd, n, window, chunk, local_on)
        + s * pallas_attention.prefix_shared_steps(
            ls, lp, hd, vd, n, window, chunk, local_on
        )
        for n in prefix_lens
    )


# ---------------------------------------------------------------------------
# Shard weight source (sync or prefetching)
# ---------------------------------------------------------------------------

def _is_floating(a: np.ndarray) -> bool:
    return np.issubdtype(a.dtype, np.floating) or a.dtype.name == "bfloat16"


# Process-wide total of host shard bytes built for upload, across every
# loader this process creates (DP/MP producer threads share it, hence the
# lock — += is not atomic under the GIL). The CLI reports it as
# ``streamed_bytes`` so a scale artifact can show the full model crossed
# the stream (e.g. 13.5 GB through a chip holding a fraction of that).
_PROCESS_STREAM_BYTES = [0]
_PROCESS_STREAM_LOCK = threading.Lock()

# Process-wide count of host-side numpy/native dtype casts the weight
# stream performed (the _HostShardLoader._cast fallback). The hot path is
# expected to keep this at ZERO — source dtypes XLA can cast are uploaded
# raw and converted on chip (_place/_cast_tree) — so tests pin the
# warm-sweep invariant against this counter.
_PROCESS_HOST_CASTS = [0]

# Process-wide count of tied-lm_head dequant->transpose->requant passes
# actually computed (a [V, D] pass per occurrence — heavy enough that the
# decode hot path must amortize it). The result is seated in the host
# shard cache keyed by the embedding file's stat, so a WARM process —
# source restarts, new executors, fresh decode calls — performs ZERO of
# these; tests pin that invariant against this counter.
_PROCESS_TIED_REQUANTS = [0]


def process_streamed_bytes() -> int:
    return _PROCESS_STREAM_BYTES[0]


def process_host_casts() -> int:
    return _PROCESS_HOST_CASTS[0]


def process_tied_head_requants() -> int:
    return _PROCESS_TIED_REQUANTS[0]


def reset_process_streamed_bytes() -> None:
    """Zero the counters — the CLI calls this at run start so a second
    cli.main() in one process doesn't report the first run's bytes."""
    with _PROCESS_STREAM_LOCK:
        _PROCESS_STREAM_BYTES[0] = 0
        _PROCESS_HOST_CASTS[0] = 0
        _PROCESS_TIED_REQUANTS[0] = 0


# The last sweeps' accounts (one record per pass over the shards, written
# when the pass ends), kept by the process because executors are built per
# call: see SweepClock for the record's fields.
_SWEEP_LOG: deque = deque(maxlen=256)
_SWEEP_LOG_LOCK = threading.Lock()


def process_sweep_log() -> list[dict]:
    """The last sweeps' accounts, oldest first (at most 256)."""
    with _SWEEP_LOG_LOCK:
        return [dict(r) for r in _SWEEP_LOG]


def _last_head_record() -> dict:
    """The newest record of a sweep that released a seated head's uploads
    (it has ``head_queued_s``), or {}: what a source plans its lead on."""
    with _SWEEP_LOG_LOCK:
        return next(
            (dict(r) for r in reversed(_SWEEP_LOG) if "head_queued_s" in r), {}
        )


# The sweeps that stalled (SweepClock.finish marks them ``slow``), newest
# last: each one's record with its per-shard table, which every other sweep
# drops. ``_SLOW_SWEEPS_SEEN`` counts them over the process's life.
_SLOW_SWEEPS: deque = deque(maxlen=8)  # guarded by: _SWEEP_LOG_LOCK
_SLOW_SWEEPS_SEEN = [0]  # guarded by: _SWEEP_LOG_LOCK
# A sweep is slow over SLOW_X times the median wall of the log's preceding
# records of the same plan AND SLOW_OVER_S seconds over it, once the log
# holds SLOW_MIN_PEERS of them (a compiling first sweep is never slow).
SLOW_X, SLOW_OVER_S, SLOW_MIN_PEERS = 1.5, 0.5, 5
_LOG = logging.getLogger(__name__)


def process_slow_sweeps() -> list[dict]:
    """The last sweeps marked ``slow`` (at most 8), oldest first: the
    sweep's record, the median record it was held against (``median``),
    the phase and shard with the largest excess (``worst_phase``,
    ``worst_shard``) and its per-shard table (``shards``)."""
    with _SWEEP_LOG_LOCK:
        return [dict(r) for r in _SLOW_SWEEPS]


# Python's collector, as one hook sees it: [seconds inside generation-2
# collections, their count, the running one's start]. Registered by the
# process's first SweepClock; a sweep reads the first two before and after.
_GC_SEEN = [0.0, 0, 0.0]


def _gc_hook(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _GC_SEEN[2] = time.perf_counter()
    else:
        _GC_SEEN[0] += time.perf_counter() - _GC_SEEN[2]
        _GC_SEEN[1] += 1


def _gc_seen() -> tuple[float, int]:
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    return _GC_SEEN[0], _GC_SEEN[1]


def stream_stats() -> dict[str, float]:
    """The process-wide stream counters as ONE registry source — shared
    by the process registry here and the serve engine's per-engine
    registry, so the two surfaces can never drift. The last sweep's
    account rides along as ``last_sweep_*`` gauges, so a link that slowed
    or a pipeline that starved shows on ``/metrics`` without a trace."""
    out = {
        "streamed_bytes": process_streamed_bytes(),
        "host_casts": process_host_casts(),
        "tied_head_requants": process_tied_head_requants(),
    }
    with _SWEEP_LOG_LOCK:
        last = _SWEEP_LOG[-1] if _SWEEP_LOG else None
        out["slow_sweeps"] = _SLOW_SWEEPS_SEEN[0]
    if last is not None:
        out.update({f"last_sweep_{k}": v for k, v in last.items()})
    return out


class ShardStamps:
    """One shard on the consumer's clock (``perf_counter``): ``t_take``
    (the ``source_wait`` span's end: the shard is the consumer's),
    ``t_launch`` (its first block's steps are enqueued: one read of the
    clock, the only one the timeline adds), ``t_wait`` / ``t_ready`` (the
    shard-end ``device_wait`` span's two ends, which wait for THIS shard's
    results; None for a shard that has no such wait; where the wait lagged
    one shard, both lie inside the next shard's ``compute`` span, after
    ``t_end``) and ``t_end`` (the ``compute`` span's end)."""

    __slots__ = ("shard_idx", "source_wait_s", "t_take", "t_launch",
                 "t_wait", "t_ready", "t_end")

    def __init__(self, shard_idx: int, source_wait_s: float, t_take: float):
        self.shard_idx, self.source_wait_s, self.t_take = (
            shard_idx, source_wait_s, t_take
        )
        self.t_launch = self.t_wait = self.t_ready = None
        self.t_end = t_take


class SweepClock:
    """The account of one pass over the shards, kept on the consumer's
    thread: the same ``perf_counter`` pairs that the pass's spans record.

    Opened where the pass starts (before the executor's construction for a
    ``run_prompts`` call's first pass, in ``StreamingExecutor.__call__``
    otherwise) and finished where its scores are on the host; as a context
    manager it closes whatever an error left open. The consumer's five
    phases partition the wall: ``head_s`` (everything before the first wait
    for a shard), then per shard ``source_wait_s`` (blocked on the weight
    source) and the ``compute`` span, split into ``device_wait_s`` (blocked
    on the device's results: the ``block_until_ready`` at the shard's end
    and the one inside the activation store, which resolves a block's
    device->host copy one store later; that part is also ``act_wait_s``;
    a block the store keeps on the chip waits for nothing and counts in
    ``act_device_bytes`` instead of ``act_bytes``)
    and ``dispatch_s`` (the rest: the host's own work, dispatching the
    steps and copying activations), then ``tail_s`` (after the last
    shard's dispatch: the source's close, the scores' fetch, the store's
    clear). The producer's side comes from the source's own account
    (``ShardWeightSource.account``). One thread opens, drives and finishes
    a clock: its profiler annotations nest on that thread.

    Beside the sums the clock keeps the sweep's timeline shard by shard
    (``ShardStamps``, one a shard the consumer takes): lined up with the
    source's uploads in ``finish()`` they say why the device stood idle
    between shards (``utils.intervals.idle_split``: ``drained_s``,
    ``own_upload_wait_s``, ``behind_upload_s``), and a sweep that stalls
    keeps them as its per-shard table (``process_slow_sweeps``); every
    other sweep drops them with its clock. The shard's last block is
    dispatched at the earlier of ``t_wait`` (the shard-end wait's start)
    and ``t_end``, which is also where the consumer has just let go of the
    shard (``ShardWeightSource.dispatched``): an upload
    ordered by that signal (the record's ``uploads_ordered``) is enqueued
    after it, and an upload's enqueue is dated where its ``device_put``
    call RETURNED. ``waits_deferred`` counts the shards whose end was
    waited for one shard later (``StreamingExecutor._stream_shard``)."""

    def __init__(self):
        self.sweep_id = obs_trace.new_sweep_id()
        self.shard_idx = -1  # the shard the consumer is on
        self.shards: list[ShardStamps] = []  # one a shard taken, in order
        # Rows of each block a shard is dispatched over (the pass's blocks,
        # in order): what idle_split weighs a block's device time by.
        self.block_rows: tuple[int, ...] = ()
        self._gc0 = _gc_seen()
        self.source_wait_s = self.compute_s = self.device_wait_s = 0.0
        self.waits_deferred = 0
        self.act_fetch_s = self.act_store_s = 0.0
        self.head_s = 0.0
        # Device-resident int32 [4] counts, one per decoder segment and
        # block, of a model that holds a share of its experts: summed and
        # read once, in finish(). ``model`` is the pass's LlamaConfig.
        self.moe_counts: list = []
        self.model = None
        # Rows x expert layers dispatched, by the expert layer's body
        # (``_expert_rows``): host counts from the shapes.
        self.expert_rows = {"grouped": 0, "dense": 0}
        # Rows x linear-attention layers dispatched, by the body that runs
        # them (the Pallas kernel or the XLA op), and the most recurrent
        # state one dispatch held: host counts from the shapes.
        self.linear_rows = {"kernel": 0, "xla": 0}
        self.linear_state_bytes = 0
        # The same two counts for delta-rule (KDA) layers, whose rows run
        # another kernel (ops/kda_attention.py).
        self.kda_rows = {"kernel": 0, "xla": 0}
        self.kda_state_bytes = 0
        # Steps of the online softmax the two scoring flash kernels run
        # (``_flash_steps``): a host count from the shapes and the prompts'
        # prefix lengths.
        self.flash_steps = 0
        # A looped model: decoder-layer visits the consumer took (a count of
        # the plan's indices, on the host), and per block the gate's
        # expected exit step summed over its real scored rows (device
        # float32 scalars, read once in finish()) with those rows' count.
        self.layer_visits = 0
        self.exit_sums: list = []
        self.exit_rows = 0
        self._sweep = obs_trace.sweep_span(self.sweep_id, mode="offline")
        self._head = obs_trace.timed(
            "sweep_head", cat="sweep", sweep_id=self.sweep_id
        )
        self._tail = None
        self._sweep.__enter__()
        self._head.__enter__()
        self.t0 = self._sweep.t0

    def __enter__(self) -> "SweepClock":
        return self

    def __exit__(self, *exc) -> bool:
        self.abandon()  # no-op after a finished pass
        return False

    def span_ids(self) -> dict:
        return {"sweep_id": self.sweep_id, "shard_idx": self.shard_idx}

    def set_block_rows(self, rows) -> None:
        """The rows of each block the pass's shards are dispatched over."""
        self.block_rows = tuple(rows)
        self._sweep.attrs["block_rows"] = ",".join(map(str, self.block_rows))

    def take(self, shard_idx: int, wait) -> None:
        """The consumer has shard ``shard_idx``; ``wait`` is the
        ``source_wait`` span that ended with it."""
        self.shard_idx = shard_idx
        self.source_wait_s += wait.dur_s
        self.shards.append(
            ShardStamps(shard_idx, wait.dur_s, wait.t0 + wait.dur_s)
        )

    def launched(self) -> float:
        """The current shard's first block is dispatched."""
        t = self.shards[-1].t_launch = time.perf_counter()
        return t

    def wait_for_shard(self, result, stamps: ShardStamps) -> None:
        """Block on ``result``, the last result of the shard ``stamps``
        stands for (the current one, or the one before where its wait
        lagged): the ``device_wait`` span at that shard's end, named by its
        index, and its ``t_wait`` / ``t_ready``."""
        with obs_trace.timed(
            "device_wait", cat="sweep", at="shard_end", sweep_id=self.sweep_id,
            shard_idx=stamps.shard_idx,
        ) as wait:
            jax.block_until_ready(result)
        self.device_wait_s += wait.dur_s
        stamps.t_wait, stamps.t_ready = wait.t0, wait.t0 + wait.dur_s

    def shard_done(self, compute) -> None:
        """``compute``: the current shard's ``compute`` span, ended."""
        self.compute_s += compute.dur_s
        self.shards[-1].t_end = compute.t0 + compute.dur_s

    def end_head(self) -> None:
        """The consumer reaches its first wait for a shard."""
        if self._head is not None:
            self._head.__exit__(None, None, None)
            self.head_s = self._head.dur_s
            self._head = None

    def start_tail(self) -> None:
        """The last shard's compute is dispatched."""
        self.end_head()  # a pass that streamed nothing still has one
        self._tail = obs_trace.timed(
            "sweep_tail", cat="sweep", sweep_id=self.sweep_id
        )
        self._tail.__enter__()

    def abandon(self) -> None:
        """Close whatever is still open and write no record (an aborted
        pass). Idempotent."""
        if self._tail is not None:
            self._tail.__exit__(None, None, None)
        self.end_head()
        if self._sweep is not None:
            self._sweep.__exit__(None, None, None)
        self._tail = self._sweep = None

    def finish(self, source, store=None) -> dict | None:
        """Scores are on the host: close the tail and the sweep, and write
        the pass's record to the process log. None when already closed.
        ``store``: the pass's activation store, whose waits for the device
        (inside ``compute``) count as ``device_wait_s``, not as the host's
        ``dispatch_s``, and which counted its own bytes: those that crossed
        the link and those it kept on the chip."""
        sweep, tail = self._sweep, self._tail
        if sweep is None:
            return None
        self.abandon()
        act_wait_s, act_bytes, act_device_bytes = (
            (store.device_wait_s, store.link_bytes, store.device_bytes)
            if store is not None
            else (0.0, 0, 0)
        )
        device_wait_s = self.device_wait_s + act_wait_s
        rec = {
            "sweep_id": self.sweep_id,
            "t_end": time.monotonic(),
            "wall_s": sweep.dur_s,
            "head_s": self.head_s,
            "source_wait_s": self.source_wait_s,
            "dispatch_s": self.compute_s - device_wait_s,
            "device_wait_s": device_wait_s,
            "tail_s": tail.dur_s if tail is not None else 0.0,
            "waits_deferred": self.waits_deferred,
            "act_fetch_s": self.act_fetch_s,
            "act_store_s": self.act_store_s,
            "act_wait_s": act_wait_s,
            "act_bytes": act_bytes,
            "act_device_bytes": act_device_bytes,
        }
        gc_s, gc_n = _gc_seen()
        rec.update(gc_s=gc_s - self._gc0[0], gc_collections=gc_n - self._gc0[1])
        account = getattr(source, "account", None)
        if account is not None:  # a shared (broadcast) source keeps none
            rec.update(account(sweep.t0, sweep.t0 + sweep.dur_s, self))
        if self.model is not None:
            rec.update(_model_account(self.model, self.moe_counts))
            rec.update(
                loop_steps=self.model.total_ut_steps,
                layer_visits=self.layer_visits,
                expert_rows_grouped=self.expert_rows["grouped"],
                expert_rows_dense=self.expert_rows["dense"],
                linear_rows_kernel=self.linear_rows["kernel"],
                linear_rows_xla=self.linear_rows["xla"],
                linear_state_bytes=self.linear_state_bytes,
                kda_rows_kernel=self.kda_rows["kernel"],
                kda_rows_xla=self.kda_rows["xla"],
                kda_state_bytes=self.kda_state_bytes,
                flash_steps=self.flash_steps,
            )
            if self.exit_sums:
                rec["exit_step_mean"] = float(
                    jnp.sum(jnp.stack(self.exit_sums))
                ) / max(self.exit_rows, 1)
        with _SWEEP_LOG_LOCK:
            peers = sorted(
                (
                    r for r in _SWEEP_LOG
                    if r.get("uploads") == rec.get("uploads")
                    and r.get("layer_visits") == rec.get("layer_visits")
                ),
                key=lambda r: r["wall_s"],
            )
            median = peers[len(peers) // 2] if len(peers) >= SLOW_MIN_PEERS else None
            rec["slow"] = int(
                median is not None
                and rec["wall_s"]
                > max(SLOW_X * median["wall_s"], median["wall_s"] + SLOW_OVER_S)
            )
            _SWEEP_LOG.append(rec)
        if rec["slow"]:
            _keep_slow_sweep(rec, median, self, source, sweep.t0 + sweep.dur_s)
        return rec


def _keep_slow_sweep(
    rec: dict, median: dict, clock: "SweepClock", source, t_end: float
) -> None:
    """A sweep stalled: keep its record with its per-shard table (the last
    8: ``process_slow_sweeps``), count it, and say once, in the journal and
    the log, which phase and which shard hold the largest excess over the
    median record ``median``'s share."""
    phases = ("head_s", "source_wait_s", "dispatch_s", "device_wait_s", "tail_s")
    worst = max(phases, key=lambda k: rec[k] - median[k])
    table = (
        source.shard_table(clock, t_end)
        if hasattr(source, "shard_table")
        else _shard_table(clock, t_end, {}, {})  # a shared source times no upload
    )
    key = worst if worst in ("source_wait_s", "dispatch_s", "device_wait_s") else None
    row = max(table, key=lambda r: r[key], default=None) if key else None
    kept = dict(
        rec,
        median={k: median[k] for k in ("sweep_id", "wall_s", *phases)},
        worst_phase=worst,
        worst_phase_excess_s=rec[worst] - median[worst],
        worst_shard=row["shard_idx"] if row else -1,
        worst_shard_s=row[key] if row else 0.0,
        # of that shard's seconds, what the host's stamps put down to uploads
        worst_shard_upload_s=(
            row["own_upload_wait_s"] + row["behind_upload_s"] if row else 0.0
        ),
        shards=table,
    )
    with _SWEEP_LOG_LOCK:
        _SLOW_SWEEPS.append(kept)
        _SLOW_SWEEPS_SEEN[0] += 1
    said = {
        k: kept[k] for k in ("sweep_id", "wall_s", "worst_phase",
                             "worst_phase_excess_s", "worst_shard",
                             "worst_shard_s", "worst_shard_upload_s", "gc_s")
    }
    obs_trace.instant("slow_sweep", cat="sweep", **said)
    obs_events.emit("slow_sweep", median_wall_s=median["wall_s"], **said)
    _LOG.warning(
        "slow sweep %d: %.3f s against a median of %.3f s; %s is %.3f s over "
        "the median sweep's, most of it in shard %d (%.3f s, of which %.3f s "
        "launched ahead of an upload's arrival); gc %.3f s "
        "(process_slow_sweeps() has the per-shard table)",
        rec["sweep_id"], rec["wall_s"], median["wall_s"], worst,
        kept["worst_phase_excess_s"], kept["worst_shard"],
        kept["worst_shard_s"], kept["worst_shard_upload_s"], rec["gc_s"],
    )


def _shard_table(
    clock: "SweepClock", t_end: float, uploads: dict, produced: dict
) -> list[dict]:
    """A sweep's timeline as one flat dict a shard: the consumer's stamps
    (``clock.shards``) as durations, the idle split against ``uploads``
    (``shard_idx -> (t_enqueue, t_done)``) and the producer's seconds for
    that shard (``produced``: ``shard_idx -> (shard_load_s,
    upload_dispatch_s, upload_ordered)``). An upload's ``t_enqueue`` is
    where its ``device_put`` call returned. ``t_end``: the sweep's end,
    which bounds the last shard's waits where it has no shard-end wait of
    its own."""
    shards = clock.shards
    split = idle_split(
        [
            # the last block is dispatched where the shard-end wait begins,
            # or, where that wait lagged into the next shard, where the
            # shard's compute span ends
            (s.shard_idx, s.t_launch, min(s.t_wait or s.t_end, s.t_end), s.t_ready)
            for s in shards
        ],
        uploads, t_end, clock.block_rows,
    )
    table = []
    lagged_in = 0.0  # the shard before's wait, where it lagged into this one
    for s, (drained, own, behind) in zip(shards, split):
        wait = s.t_ready - s.t_wait if s.t_ready is not None else 0.0
        lagged = s.t_wait is not None and s.t_wait > s.t_end
        load, put, ordered = produced.get(s.shard_idx, (0.0, 0.0, 0))
        table.append({
            "shard_idx": s.shard_idx,
            "source_wait_s": s.source_wait_s,
            # the span's time less the waits for the device inside it
            "dispatch_s": s.t_end - s.t_take - lagged_in - (0.0 if lagged else wait),
            "device_wait_s": wait,
            "drained_s": drained,
            "own_upload_wait_s": own,
            "behind_upload_s": behind,
            "shard_load_s": load,
            "upload_dispatch_s": put,
            "upload_ordered": ordered,
        })
        lagged_in = wait if lagged else 0.0
    return table


def _model_account(model: LlamaConfig, moe_counts: list) -> dict:
    """What a sweep's record says of the model it ran: layers by attention
    kind, the expert layer's share, and (one device read, at the sweep's
    end) how many of the router's assignments landed on a held expert."""
    sliding = llama.layer_sliding_pattern(model)
    linear = sum(model.layer_linear or ())
    kda = model.linear_kind == "kda"
    heads, _, d, _ = model.attn_shape(linear=True)
    rec = {
        "window_layers": sum(sliding),
        "full_layers": len(sliding) - sum(sliding) - linear,
        "linear_layers": linear,
        "softmax_layers": len(sliding) - linear,
        "kda_layers": linear if kda else 0,
        # What a KDA layer's prefix hands each prompt's suffixes beside the
        # state: the last (taps - 1) pre-convolution rows of q, k and v, in
        # the compute dtype's two bytes.
        "conv_tail_bytes": (model.linear_conv_size - 1) * 3 * heads * d * 2 * bool(kda and linear),
        "hc_streams": model.hc_mult,
        "experts_held": len(model.held_experts),
        "router_width": model.num_local_experts,
    }
    if model.num_local_experts:
        per_group = model.num_local_experts // model.moe_n_group
        held = model.held_experts
        rec["router_groups"] = model.moe_n_group
        rec["groups_held"] = -(-held.stop // per_group) - held.start // per_group
    if moe_counts:
        hits, routed, tile_rows, touched = np.asarray(_sum_counts(moe_counts))
        rec.update(
            held_expert_hits=int(hits), routed_assignments=int(routed),
            expert_tile_rows=int(tile_rows), experts_touched=int(touched),
        )
    return rec


@jax.jit
def _sum_counts(counts: list) -> jax.Array:
    """A sweep's expert counts (one a block and decoder segment: ~100 in a
    batch of long prompts) summed in one dispatch. Stacked op by op they
    kept the chip's host ~30 ms a batch after the sweep's record had closed
    (``ling-3.0-flash-vl.score-long-b8``)."""
    return jnp.sum(jnp.stack(counts), axis=0)


# The process-wide stream counters are registry citizens (obs/registry.py):
# the serve metrics endpoint and the batch CLI's --metrics_out both expose
# streamed bytes from here, the same numbers the stats lines print.
_OBS_REGISTRY.register("stream", stream_stats)

# What each field of a sweep's record means, as the gauges' HELP lines.
SWEEP_RECORD_HELP = {
    "wall_s": "The last sweep, from before its executor was built to its "
    "scores on the host; head_s + source_wait_s + dispatch_s + "
    "device_wait_s + tail_s.",
    "head_s": "Consumer: before its first wait for a shard (executor and "
    "loader construction, tokenising, the source's start).",
    "source_wait_s": "Consumer: blocked on the weight source (compute "
    "starved for weights).",
    "dispatch_s": "Consumer: the host's own work inside the shards' compute "
    "(dispatching steps, copying activations); waits for the device are "
    "NOT in here but in device_wait_s.",
    "device_wait_s": "Consumer: blocked on the device's results, at each "
    "shard's end (or the next shard's, where the wait lagged: "
    "waits_deferred) and inside the activation store; near wall_s the sweep "
    "is device- or link-bound (see upload_busy_s), not host-bound.",
    "tail_s": "Consumer: after the last shard's dispatch (source close, "
    "scores to the host, store clear).",
    "drained_s": "Device idle, by the host's stamps: from each shard-end wait's "
    "return (the device's last result is on the host, nothing is enqueued "
    "behind it) to the next shard's first block dispatched; holds the "
    "source_wait, the store's bookkeeping and that block's host dispatch. "
    "Summed over drained_shards boundaries; 0 at a boundary whose wait "
    "lagged (waits_deferred): the next shard was launched before it returned.",
    "waits_deferred": "Shards whose end was not waited for at the end: where "
    "the source answered lag or skip (ShardWeightSource.end_wait). Lag: the "
    "last result blocked on inside the next shard's dispatch, before its "
    "last block, so the device had the next shard queued while the host "
    "woke; only a shard that uploaded nothing, kept its blocks on the chip "
    "and is followed by two builds that upload nothing (no upload goes out "
    "behind queued launches), never the last; a pass whose every layer is "
    "seated lags every shard-end wait it has. Skip: a shard of a seated "
    "head before its last, whose wait covers it (lead_planned).",
    "drained_shards": "Shard boundaries counted in drained_s (a shard with a "
    "wait for the device at its end, followed by one that launched).",
    "own_upload_wait_s": "Device idle, by the host's stamps: shards whose "
    "first steps were enqueued before their OWN weight upload had arrived, "
    "from the launch to that arrival (no later than the shard-end wait's "
    "return); the link's honest turn. 0 for a shard served from the "
    "residency tier.",
    "behind_upload_s": "Device idle, by the host's stamps: shards whose own "
    "weights had arrived but whose launches queued behind ANOTHER shard's "
    "upload, enqueued (its device_put call returned) before the shard's "
    "last block was dispatched and arrived before the shard was done: from "
    "the first launch (or the own arrival, or that enqueue if later, less "
    "the blocks dispatched before it) to that upload's arrival. With the "
    "uploads ordered (uploads_ordered) that upload went out behind an "
    "EARLIER shard's steps and is the link's turn, not an accident of order.",
    "launches_behind_upload": "Shards whose behind_upload_s share is over 1 ms.",
    "uploads_ordered": "Weight uploads whose device_put went out on a place "
    "the consumer gave back with dispatched() (the source's one gate: a "
    "free place, of prefetch_depth + 1, and behind a seated head the head's "
    "release), that is behind the last block of an earlier shard (against "
    "uploads; the first prefetch_depth + 1 builds of a source use the "
    "places it starts with, and a consumer that never says dispatched "
    "orders none).",
    "lead_planned": "The lead the gate gave the streamed run behind a seated "
    "head (seated shards that hold a decoder layer open the sweep): its "
    "uploads go out once the head's last shard is dispatched, one, or two "
    "or three while the head's work still queued (the previous sweep's "
    "head_queued_s) outlasts them, and each later one once the shard lead "
    "before it is; 0 without such a head.",
    "uploads_led": "Uploads let out under lead_planned before the consumer "
    "took the run's first shard.",
    "head_queued_s": "The wait at the seated head's last shard, which starts "
    "where its dispatch released the run's uploads: the head's device work "
    "still queued then (the next sweep's lead is planned on it).",
    "gc_s": "Seconds inside Python's generation-2 collections that ended "
    "during the sweep, on any thread (one gc.callbacks hook a process).",
    "gc_collections": "Generation-2 collections that ended during the sweep.",
    "slow": "1 when the sweep's wall_s is over 1.5 x the median of the log's "
    "preceding records of the same plan (same uploads and layer_visits, at "
    "least five) and 0.5 s over it: its per-shard table is then in "
    "process_slow_sweeps(); 0 otherwise.",
    "act_fetch_s": "Consumer: inside the activation store's fetches "
    "(host->device for a block not kept on the chip), waits included.",
    "act_store_s": "Consumer: inside the activation store's stores "
    "(device->host for a block not kept on the chip), waits included.",
    "act_wait_s": "The part of device_wait_s spent inside the activation "
    "store.",
    "act_bytes": "Activation bytes that crossed the link, both ways: the "
    "blocks the activation store sent to the host (0 when it kept them all "
    "on the chip).",
    "act_device_bytes": "Activation bytes of the blocks the store kept on "
    "the chip between shards, counted as they were stored; beside act_bytes "
    "it says how a pass split.",
    "host_build_s": "Producer: host shard builds (mmap, verify, stack).",
    "upload_dispatch_s": "Producer: inside jax.device_put calls, which "
    "return at the enqueue; not a transfer time.",
    "producer_blocked_s": "Producer: holding a built shard while the "
    "prefetch queue was full, or a shard's host side built and waiting at "
    "its upload's gate (a place the consumer's dispatched() gives back).",
    "link_wait_s": "Consumer, inside source_wait_s: a shard in hand, waiting "
    "for the newest weight upload to its device to arrive before taking it "
    "(steps dispatched under an upload in flight would wait for the next "
    "upload too): the link's turn where the link binds. Where it is the "
    "larger part of drained_s, the boundaries wait for the link, not the host.",
    "upload_busy_s": "Union of the weight uploads' intervals (dispatch to "
    "arrival) inside the sweep: the time the link carried weights.",
    "upload_bytes": "Host bytes handed to device_put for streamed layers "
    "(upload_bytes / upload_busy_s is the link's rate while it carries).",
    "upload_pinned_bytes": "The part of upload_bytes whose tree the host "
    "cache held in the chip's pinned_host memory (moved with a memory-space "
    "device_put, no staging copy); upload_pinned_bytes / upload_bytes is 1 "
    "once every streamed layer's copy is made.",
    "uploads": "Weight uploads seen to completion.",
    "upload_misses": "Uploads whose arrays were deleted before the "
    "completion thread could wait on them.",
    "pinned_bytes": "Bytes the residency tier holds on the chip (its "
    "heaviest target) when the sweep ends: what this sweep's stream did "
    "not have to carry once seated.",
    "pin_hits": "Planned layers this sweep merged from the residency tier "
    "instead of uploading (a layer the sweep seated is not a hit).",
    "visits_pinned": "Decoder-layer visits this sweep's source served from a "
    "seat of the residency tier (nothing read, nothing uploaded); a looped "
    "model visits a layer total_ut_steps times a sweep.",
    "visits_streamed": "Decoder-layer visits served from an upload (a "
    "streamed layer, or a planned one in the sweep that seats it): a "
    "looped model's streamed layers cross the link once a step.",
    "loop_steps": "Times the sweep visits the decoder stack (the model's "
    "total_ut_steps; 1 for every model but a looped one).",
    "layer_visits": "Decoder-layer visits the consumer took this sweep "
    "(loop_steps x decoder layers for a whole pass).",
    "exit_step_mean": "A looped model's exit gate: the expected exit step "
    "(sum over steps of step x exit probability, steps from 1) averaged "
    "over the sweep's real scored rows; summed on the device, read once at "
    "the sweep's end.",
    "window_layers": "Decoder layers with local (sliding-window or chunked) "
    "attention in the model the sweep ran.",
    "full_layers": "Decoder layers with full causal attention.",
    "linear_layers": "Decoder layers with linear attention (a decayed "
    "recurrent state in place of keys and values).",
    "softmax_layers": "Decoder layers with softmax attention, window and "
    "full together.",
    "kda_layers": "Of linear_layers, those that keep a delta-rule state with "
    "a decay per key channel behind a short convolution (KDA, "
    "ops/kda_attention.py); 0 for a lightning-attention model.",
    "conv_tail_bytes": "What a KDA layer's prefix hands a prompt's suffixes "
    "beside the state: the last (taps - 1) pre-convolution rows of q, k and "
    "v at 2 bytes an element, a prompt and layer; inside the layer call, as "
    "the state; 0 for a model without such layers.",
    "hc_streams": "Residual streams between layers (hc_mult: a block's row "
    "is hc_streams x hidden_size wide in the activation store); 1 for the "
    "plain residual.",
    "experts_held": "Routed experts this process holds of each expert layer "
    "(all of them unless the model's config gives it a share).",
    "router_width": "Experts the router scores (0 for a dense model).",
    "held_expert_hits": "Router assignments that landed on a held expert, "
    "summed on the device over the sweep's expert layers and rows (padding "
    "rows included); only where a share is held.",
    "routed_assignments": "All router assignments of those rows (rows x "
    "experts per token x expert layers).",
    "expert_tile_rows": "Rows the routed body's grouped matmuls cover in "
    "whole 128-row tiles: (held expert, row tile) visits x 128, summed on the "
    "device over the sweep's expert layers and blocks; held_expert_hits over "
    "it is the share of the computed rows that belong to an assignment; 0 "
    "in the compute-all body; only where a share is held.",
    "experts_touched": "Held experts given at least one row, summed on the "
    "device over the sweep's expert layers and blocks (the routed body); "
    "each is one read of that expert's weights a call.",
    "router_groups": "Groups of the router's experts (n_group: a token "
    "picks its experts from its topk_group best groups); 1 = no group "
    "limit.",
    "groups_held": "Of router_groups, those with an expert this process "
    "holds.",
    "expert_rows_grouped": "Rows x expert layers dispatched with the routed "
    "expert body (a row computed in its chosen experts only, as grouped "
    "matmuls over the block's rows sorted by expert); padding rows included, "
    "counted on the host from the shapes.",
    "expert_rows_dense": "Rows x expert layers dispatched with the compute-"
    "all body (every row in every held expert): a block under a tensor-"
    "parallel mesh, or a softmax-router family; 0 in a scoring sweep of a "
    "sigmoid-router model on one chip, DP or MP.",
    "linear_rows_kernel": "Rows x linear-attention layers dispatched with the "
    "Pallas kernel (ops/lightning_attention.py); padding rows included, "
    "counted on the host from the shapes.",
    "linear_rows_xla": "Rows x linear-attention layers dispatched with the "
    "XLA op of the same mathematics (use_pallas off, a tensor-parallel mesh, "
    "or shapes the kernel does not take); 0 in a scoring sweep on one chip.",
    "linear_state_bytes": "The most recurrent state one dispatch of linear-"
    "attention layers held: prompts in the block x heads x qk dim x v dim x "
    "4 bytes (float32), inside the layer call; it never enters the "
    "activation store.",
    "kda_rows_kernel": "Rows x KDA layers dispatched with the Pallas kernel "
    "(ops/kda_attention.py: kda_chunk); padding rows included, counted on "
    "the host from the shapes. A KDA model reads 0 in linear_rows_*.",
    "kda_rows_xla": "Rows x KDA layers dispatched with the XLA op of the same "
    "mathematics (use_pallas off, a tensor-parallel mesh, or shapes the "
    "kernel does not take); 0 in a scoring sweep on one chip.",
    "kda_state_bytes": "The most delta-rule state one dispatch of KDA layers "
    "held: prompts in the block x heads x qk dim x v dim x 4 bytes "
    "(float32), inside the layer call.",
    "flash_steps": "Steps of the online softmax the two scoring flash "
    "kernels ran this sweep (every query head's loop trips over key tiles, "
    "a suffix's own keys one step): a host count from the shapes, the "
    "prompts' prefix lengths and the tiles ops/pallas_attention.flash_tiles "
    "picks; the kernels' device seconds over it is the cost of a step.",
}
_describe_gauges(
    "stream", {f"last_sweep_{k}": v for k, v in SWEEP_RECORD_HELP.items()}
)
_describe_gauges(
    "stream",
    {"slow_sweeps": "Sweeps marked slow since the process started (see "
     "last_sweep_slow); the last 8 keep their per-shard table."},
)


def _check_precision_plan(model_path: str, manifest: dict) -> None:
    """Validate an embedded PrecisionPlan against the integrity manifest's
    recorded per-layer dtype kinds; a disagreement raises the typed
    ``PrecisionMismatch`` (ShardLoadError family, so serving degrade
    paths apply). No-op for uniform checkpoints (no plan file) and for
    pre-dtype manifests (back-compat)."""
    from flexible_llm_sharding_tpu.runtime.precisionplan import (
        PrecisionPlan,
        plan_manifest_problems,
    )

    try:
        plan = PrecisionPlan.load(model_path)
    except (ValueError, OSError) as e:
        # A torn/corrupt embedded plan — or one that EXISTS but cannot
        # be read (EACCES/EIO; load maps only FileNotFoundError to
        # "uniform checkpoint") — is a plan that cannot vouch for the
        # checkpoint: type it, so the serve loop's degrade handler
        # (ShardLoadError family) fails the wave instead of the engine
        # dying on a bare ValueError, and the audit (verify._load_plan)
        # and the load path agree on the handling.
        raise integrity_manifest.PrecisionMismatch(str(e)) from e
    if plan is None:
        return
    problems = plan_manifest_problems(plan, manifest)
    if problems:
        _, detail = problems[0]
        raise integrity_manifest.PrecisionMismatch(
            f"{model_path}: {detail} — the checkpoint does not match its "
            "embedded precision plan (audit with the `verify` CLI "
            "subcommand)"
        )


# Float dtypes the on-device cast path handles: uploaded in their stored
# dtype (fp16/bf16 travel at half of fp32's link bytes; fp16<->bf16 at the
# SAME bytes) and converted to the compute dtype inside one jitted program
# after placement. Anything outside this set (fp64 checkpoints, exotic
# dtypes) falls back to the host cast. A host cast is a pass over every
# byte on the producer's thread where the stored bytes could go page cache
# -> DMA untouched, so even the fp32->bf16 case — which uploads 2x the
# bytes — wins whenever the link outruns the host caster; XLA's convert is
# RNE, bit-identical to the numpy/native cast it replaces.
_DEVICE_CASTABLE = frozenset({"float16", "bfloat16", "float32"})


class _HostShardLoader:
    """Host side of weight streaming: disk -> numpy segments, cast to the
    compute dtype, contiguous decoder runs pre-stacked [k, ...] for scan.

    A native readahead pool (utils/native.py, posix_fadvise(WILLNEED) — the
    kernel reads ahead asynchronously, ~zero CPU) warms the NEXT shard's
    layer files into the page cache while this shard is being cast/stacked,
    so cold-cache disk latency overlaps host compute without stealing it."""

    def __init__(self, model_path: str, layer_names: Sequence[str], np_dtype,
                 tied_embeddings: bool = False, layer_sliding=None,
                 layer_rope=None,
                 layer_linear=None,
                 layer_index: bool = False,
                 retry_policy: RetryPolicy | None = None,
                 injector: FaultInjector | None = None,
                 retry_recorder=None, retry_abort=None,
                 integrity=None, verify_weights: bool = True,
                 host_cache=None, readahead_threads: int = 2,
                 device_cast: bool = True, pinned_host=None):
        # host_cache: a runtime.hostcache.HostShardCache (or None) —
        # build_host_shard consults it before touching disk and inserts
        # verified-clean trees after a build; quarantine invalidates.
        # pinned_host: the sharding of the ONE chip's ``pinned_host`` memory
        # that every tree of this loader is uploaded to (_pinned_host_of;
        # None for a source with several targets, a placement, or a backend
        # without that memory). Where set, the cache is asked to hold the
        # streamed layers' trees there and a hit may return jax.Array leaves
        # in that memory instead of NumPy ones (same bytes; _place moves
        # them with a memory-space device_put). The cache's keys stay
        # chip-free: the cache itself keeps such a tree from a reader with
        # another target (hostcache.HostShardCache.get).
        # device_cast: True defers XLA-castable float dtypes to the on-chip
        # cast in _place. False takes the host-side numpy/native cast for
        # every mismatched dtype: the reference that the device cast is held
        # to, bit for bit (tests/test_hostcache.py); no entry point passes it.
        self.model_path = model_path
        self._host_cache = host_cache
        self._pinned_host = pinned_host
        self.device_cast = device_cast
        # Host-cast fallback accounting (the warm path must not take it).
        self.host_casts = 0
        # Transient-I/O hardening: every layer-file read retries under the
        # policy (faults/retry.py) and raises a typed ShardLoadError only on
        # exhaustion; the (test/chaos-only) injector fires the 'shard_read'
        # site inside the retried region so injected faults are absorbed
        # exactly like real ones. retry_abort (callable -> bool): the owning
        # source's stop flag — a closing source must not wait out backoff
        # sleeps before its producer thread can exit.
        self._retry = retry_policy or RetryPolicy()
        self._injector = injector
        self._recorder = retry_recorder
        self._retry_abort = retry_abort
        # Integrity verification (integrity/manifest.py): every load's
        # tensors checksum against the dir's manifest; a mismatch is an
        # IOError, so it re-reads under the SAME retry policy as real I/O
        # blips (a re-read heals page-cache/NFS corruption); only a
        # mismatch that survives exhaustion quarantines the path and
        # raises the typed ShardCorruptError. ``integrity`` is a
        # metrics.IntegrityRecorder (or None — counters dropped).
        self._integrity = integrity
        self.quarantined: set[str] = set()
        self._manifest = None
        if verify_weights:
            self._manifest = integrity_manifest.load_manifest(model_path)
            if self._manifest is None:
                import warnings

                # One-time (per loader) back-compat warning: dirs prepared
                # before the integrity layer still load, just unverified.
                warnings.warn(
                    f"{model_path}: no {integrity_manifest.MANIFEST_NAME} — "
                    "weight integrity verification skipped for this stream "
                    "(re-run split/save to emit a manifest, or audit with "
                    "the `verify` CLI subcommand)",
                    stacklevel=3,
                )
            else:
                # Mixed-precision dirs embed their PrecisionPlan: check
                # the plan's layer->dtype mapping against the manifest's
                # recorded per-layer dtype kinds ONCE here (two JSON
                # files, no tensor reads), so a plan/manifest mismatch is
                # a typed error at source construction — before a single
                # wrong-precision byte crosses the link. The per-file
                # bytes-vs-manifest check runs in load_layer per load.
                _check_precision_plan(model_path, self._manifest)
        self.layer_names = list(layer_names)
        self.np_dtype = np.dtype(np_dtype)
        self.tied = tied_embeddings
        self.layer_sliding = layer_sliding  # per-decoder local-attn flags or None
        self.layer_rope = layer_rope  # per-decoder rope flags (llama4 NoPE)
        # per-decoder linear-attention flags or None; where set, or where
        # ``layer_index`` says a layer reads its row of a per-layer table
        # (``llama.layer_indexed``), a decoder segment carries its layers'
        # indices (their decays and clamps follow them)
        self.layer_linear = layer_linear
        self.layer_index = layer_index or layer_linear is not None
        self._tied_head: Params | None = None
        self.load_time = 0.0  # file->numpy wall time (cf. load_weights_time,
        # /root/reference/utils.py:223,304)
        self.build_time = 0.0  # the shard_load spans' total: every host
        # build's wall, cache hits included (the account's host_build_s)
        self.trace_ids: dict = {}  # sweep_id / shard_idx of the build under
        # way, set by the owning source's producer for the spans below
        self.bytes_loaded = 0  # post-cast host bytes built for upload; for a
        # single-chip stream this IS the host->HBM link traffic (quantized
        # leaves travel packed, so int8/int4 count their narrow bytes)
        from flexible_llm_sharding_tpu.utils.native import FilePrefetcher

        # Readahead warms via posix_fadvise(WILLNEED) only — async kernel
        # readahead, ~zero CPU — so it is on at ANY core count (a
        # pread-based warm steals the caster's core on a 1-core host).
        self._prefetcher = FilePrefetcher(threads=readahead_threads)
        # Shard-cache key prefix: everything besides the layer index tuple
        # that shapes a built host tree. The manifest is identified by its
        # FILE stat (atomic writes = new mtime), mirroring the crc verdict
        # cache, so a re-prepared dir can never alias an old entry; per-
        # layer-file stats are guarded at hit time by the cache itself.
        manifest_stat = None
        try:
            st = os.stat(
                os.path.join(model_path, integrity_manifest.MANIFEST_NAME)
            )
            manifest_stat = (st.st_mtime_ns, st.st_size)
        except OSError:
            pass
        self._cache_key_base = (
            os.path.abspath(model_path),
            np.dtype(np_dtype).name,
            bool(tied_embeddings),
            tuple(layer_sliding) if layer_sliding is not None else None,
            tuple(layer_rope) if layer_rope is not None else None,
            tuple(layer_linear) if layer_linear is not None else None,
            self.layer_index,
            manifest_stat,
            bool(verify_weights and self._manifest is not None),
            device_cast,
        )

    def close(self) -> None:
        """Retire the readahead pool. Idempotent: a second close (source
        close racing a recovery close) and a warm() after close are both
        no-ops."""
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def warm(self, layer_idxs: tuple[int, ...]) -> None:
        """Queue a shard's files for page-cache readahead (non-blocking)."""
        if self._prefetcher is None:
            return
        self._prefetcher.prefetch(
            *(
                os.path.join(
                    self.model_path,
                    f"{self.layer_names[i]}{checkpoint.LAYER_FILE_SUFFIX}",
                )
                for i in layer_idxs
            )
        )

    def _layer_file(self, name: str) -> str:
        """The file a layer name actually reads — the quarantine key (and,
        via the same shared mapping, the residency planner's byte
        estimates)."""
        return checkpoint.layer_file_for(self.model_path, name, self.tied)

    def _load_one(self, name: str) -> Params:
        path = self._layer_file(name)
        if path in self.quarantined:
            # Persistent corruption already proven: fail fast instead of
            # re-paying the whole retry ladder on every sweep. A fresh
            # loader (e.g. the serving engine's source restart) gets a
            # clean slate, so a repaired file is picked up again.
            raise ShardCorruptError(
                f"{path}: quarantined after persistent checksum mismatches"
            )
        mismatches = {"n": 0}

        def attempt() -> Params:
            try:
                if self._injector is not None:
                    self._injector.fire("shard_read", detail=name)
                    self._injector.fire("host_oom", detail=name)
                return self._load_one_raw(name)
            except ChecksumMismatch:
                mismatches["n"] += 1
                if self._integrity is not None:
                    self._integrity.count("integrity_failures")
                raise
            except MemoryError as e:
                # Host allocation failure (real, or the injected host_oom
                # site above): typed into the RETRYABLE family — after
                # the brownout ladder frees host RAM (cache shrink, pin
                # eviction), a retry can succeed — and reported as a
                # pressure event so the ladder engages. Before this, a
                # MemoryError here escaped raw and was engine-FATAL.
                _note_pressure_event("host_oom")
                raise HostOOMError(
                    f"host OOM loading {name}: {e}"
                ) from e

        try:
            out = retry_call(
                attempt,
                policy=self._retry,
                label="shard_read",
                recorder=self._recorder,
                wrap=ShardLoadError,
                abort=self._retry_abort,
            )
        except ShardLoadError as e:
            if isinstance(e.__cause__, ChecksumMismatch) and mismatches["n"] >= 2:
                # At least TWO independent reads came back wrong: the bytes
                # ON DISK are corrupt, not a transient blip. Quarantine the
                # path and surface the typed signal (still a
                # ShardLoadError, so the serving degrade path applies
                # unchanged). A single mismatch cut short by an abort (a
                # closing source) or the retry deadline is NOT re-read
                # evidence — it re-raises untyped and a later load retries
                # the path fresh.
                self.quarantined.add(path)
                # Proven-bad bytes must not survive in EITHER cache: drop
                # every host-resident shard built from this file and its
                # crc verdicts, so a repaired file re-verifies from scratch.
                if self._host_cache is not None:
                    self._host_cache.invalidate_path(path)
                integrity_manifest.invalidate_verdict(path)
                if self._integrity is not None:
                    self._integrity.count("quarantined_shards")
                obs_trace.instant(
                    "quarantine", cat="integrity", layer=name,
                    mismatches=mismatches["n"],
                )
                obs_events.emit(
                    "quarantine", layer=name, path=path,
                    mismatches=mismatches["n"],
                )
                raise ShardCorruptError(
                    f"{path}: checksum mismatch survived every re-read — "
                    "on-disk corruption; path quarantined (audit with the "
                    "`verify` CLI subcommand, then re-prepare the shard)"
                ) from e
            raise
        if mismatches["n"]:
            # At least one read came back corrupt and a re-read healed it
            # (page-cache/NFS corruption) — count the save, it is the
            # integrity layer's whole value proposition.
            if self._integrity is not None:
                self._integrity.count("reread_heals")
            obs_trace.instant(
                "reread_heal", cat="integrity", layer=name,
                mismatches=mismatches["n"],
            )
            obs_events.emit(
                "reread_heal", layer=name, mismatches=mismatches["n"]
            )
        return out

    def _load_one_raw(self, name: str) -> Params:
        corrupt = None
        if self._injector is not None:
            corrupt = lambda flat, _n=name: self._injector.corrupt_flat(  # noqa: E731
                "corrupt_shard", flat, detail=_n
            )
        if name == "lm_head" and self.tied:
            if self._tied_head is not None:
                return self._tied_head
            # Cross-loader amortization: the built head (requantized or
            # transposed) is seated in the process host shard cache keyed
            # by the embedding FILE's stat, so a fresh loader — a serve
            # source restart, a new decode call — reuses it instead of
            # re-paying the [V, D] dequant+transpose+requant. Skipped
            # under chaos injection (the cache is off there anyway, and a
            # seeded corrupt_shard draw must hit a real load).
            cache = self._host_cache if self._injector is None else None
            embed_path = self._layer_file("model.embed_tokens")
            cache_key = guard = None
            if cache is not None:
                from flexible_llm_sharding_tpu.runtime.hostcache import (
                    stat_guard,
                )

                cache_key = self._cache_key_base + ("__tied_head__",)
                guard = stat_guard([embed_path])
                hit = cache.get(cache_key) if guard is not None else None
                if hit is not None:
                    self._tied_head = hit[0]
                    return self._tied_head
            emb = checkpoint.load_layer(
                self.model_path,
                "model.embed_tokens",
                manifest=self._manifest,
                corrupt=corrupt,
            )
            e = emb["embedding"]
            if checkpoint.is_quantized_leaf(e):
                # Quantized checkpoints carry scales laid out for [V, D];
                # the head kernel [D, V] needs the transposed layout, so
                # requantize the transpose to keep the transfer narrow.
                # ALWAYS to int8 — even from an int4 source: two independent
                # group-wise roundings compound, and at 4 bits the second
                # rounding can double the error on the most quality-
                # sensitive matrix (ADVICE r4). Requantizing to int8 keeps
                # the second-rounding error negligible for one matrix's
                # worth of extra link bytes per decode step. Cached: weights
                # are immutable for the loader's lifetime, and the decode
                # loop re-streams lm_head every token — a dequant+transpose+
                # requant of [V, D] per token would land on the hot path.
                with _PROCESS_STREAM_LOCK:
                    _PROCESS_TIED_REQUANTS[0] += 1
                deq = np.ascontiguousarray(checkpoint.dequantize_np(e).T)
                q, s = checkpoint._quantize_int8(deq)
                self._tied_head = {"kernel": {"q8": q, "s": s}}
            else:
                self._tied_head = {"kernel": np.ascontiguousarray(e.T)}
            if cache is not None and guard is not None:
                # Seated only after the embed load's integrity check
                # passed (load_layer raised otherwise); charged at its
                # real packed bytes. The guard binds to the embed file's
                # pre-read stat, so a re-prepared dir invalidates.
                kern = self._tied_head["kernel"]
                nbytes = (
                    int(kern["q8"].nbytes + kern["s"].nbytes)
                    if checkpoint.is_quantized_leaf(kern)
                    else int(kern.nbytes)
                )
                cache.put(
                    cache_key, self._tied_head, nbytes=nbytes, guard=guard
                )
            return self._tied_head
        return checkpoint.load_layer(
            self.model_path, name, manifest=self._manifest, corrupt=corrupt
        )

    def _cast(self, tree: Params) -> Params:
        from flexible_llm_sharding_tpu.utils.native import convert_array

        def one(a):
            if checkpoint.is_quantized_leaf(a):
                return a  # int8 payload + fp32 scale travel as stored
            if not (_is_floating(a) and a.dtype != self.np_dtype):
                return a
            if (
                self.device_cast
                and a.dtype.name in _DEVICE_CASTABLE
                and self.np_dtype.name in _DEVICE_CASTABLE
            ):
                # On-device cast path: upload the stored bytes untouched
                # (zero host CPU per byte — for mmap layouts the pages go
                # page cache -> DMA with no host pass at all) and convert
                # inside the jitted cast after placement (_place). This
                # retires the host cast from the hot path entirely.
                return a
            # Host fallback (dtypes XLA can't be handed directly): native
            # parallel cast (bit-exact RNE, C++ worker slices) — numpy's
            # single-threaded astype (~1 GB/s for fp16->bf16) caps the
            # weight stream as soon as the host->HBM link is faster.
            self.host_casts += 1
            with _PROCESS_STREAM_LOCK:
                _PROCESS_HOST_CASTS[0] += 1
            out = convert_array(a, self.np_dtype)
            return out if out is not None else a.astype(self.np_dtype)

        return jax.tree.map(one, tree, is_leaf=checkpoint.is_quantized_leaf)

    def build_host_shard(
        self, layer_idxs: tuple[int, ...], streamed: bool = True,
        upload: bool = True,
    ) -> list[tuple[str, Any]]:
        # Traced wrapper: one "shard_load" span per host build (cache hits
        # included — their near-zero duration IS the cache's evidence in
        # the timeline; the hostcache emits its own hit/miss instants).
        # streamed=False: a layer the residency tier is about to seat. It is
        # uploaded once and then resident, so its tree is worth no pinned
        # copy, and the host cache takes it only where there is room (a
        # restart or a re-seat finds it there): the 10-11 GB of a seating
        # sweep push out no entry of a layer that crosses the link every
        # sweep.
        # upload=False: built ahead of its place in the sweep, for the cache
        # alone (ShardWeightSource._build_streamed_first); the build that
        # uploads it follows and counts its bytes.
        with obs_trace.timed(
            "shard_load",
            cat="stream",
            first=layer_idxs[0] if layer_idxs else -1,
            n=len(layer_idxs),
            **self.trace_ids,
        ) as sp:
            out = self._build_host_shard(layer_idxs, streamed, upload)
        self.build_time += sp.dur_s
        return out

    def _count_streamed(self, shard_bytes: int) -> None:
        self.bytes_loaded += shard_bytes
        with _PROCESS_STREAM_LOCK:
            _PROCESS_STREAM_BYTES[0] += shard_bytes

    def _ask_pinned(self, cache_key, segments) -> None:
        """Ask the cache to hold a streamed shard's tree in the target
        chip's ``pinned_host`` memory (hostcache.HostShardCache.pin: made
        once, off this thread). Only a tree that travels as stored: one
        with quantized leaves is dequantized on placement and keeps its
        NumPy form."""
        if not any(_has_quantized(seg) for _, seg in segments):
            self._host_cache.pin(cache_key, segments, self._pinned_host)

    def _build_host_shard(
        self, layer_idxs: tuple[int, ...], streamed: bool = True,
        upload: bool = True,
    ) -> list[tuple[str, Any]]:
        from flexible_llm_sharding_tpu.runtime.hostcache import stat_guard

        cache = self._host_cache
        cache_key = guard = None
        pin = streamed and self._pinned_host is not None
        if cache is not None:
            cache_key = self._cache_key_base + (tuple(layer_idxs),)
            # Guard stats captured BEFORE any byte is read: a concurrent
            # atomic re-prepare then leaves the entry keyed to the OLD
            # generation's stat, so the next get() invalidates instead of
            # crediting the new file with a tree built from old bytes.
            guard = stat_guard(
                [self._layer_file(self.layer_names[i]) for i in layer_idxs]
            )
            hit = cache.get(cache_key, self._pinned_host)
            if hit is not None:
                segments, shard_bytes = hit
                # The bytes still cross the host->HBM link every sweep —
                # only the disk read/parse/verify/stack work is skipped —
                # so the streamed-bytes witness keeps counting them.
                if upload:
                    self._count_streamed(shard_bytes)
                if pin and _on_pinned_host(segments) is None:
                    self._ask_pinned(cache_key, segments)
                return segments
        segments = []
        run: list[Params] = []
        run_decoder_idx: list[int] = []

        def flush():
            if run:
                # k=1 shards (layer_num_per_shard=1, the headline low-HBM
                # config) take a [None] VIEW instead of np.stack's copy —
                # with the mmap loader that keeps the whole host path
                # copy-free: page cache -> device DMA.
                stacked = jax.tree.map(
                    lambda *xs: xs[0][None] if len(xs) == 1 else np.stack(xs),
                    *run,
                )
                flags = None
                if self.layer_sliding is not None:
                    flags = np.asarray(
                        [self.layer_sliding[i] for i in run_decoder_idx], bool
                    )
                rflags = None
                if self.layer_rope is not None:
                    rflags = np.asarray(
                        [self.layer_rope[i] for i in run_decoder_idx], bool
                    )
                seg = {"layers": stacked, "sliding": flags, "rope": rflags}
                if self.layer_index:
                    seg["index"] = np.asarray(run_decoder_idx, np.int32)
                segments.append(("decoders", seg))
                run.clear()
                run_decoder_idx.clear()

        t0 = time.perf_counter()
        try:
            for idx in layer_idxs:
                name = self.layer_names[idx]
                params = self._cast(self._load_one(name))
                if name.startswith("model.layers."):
                    if run and not _stackable(run[-1], params):
                        # Mixed stacks can't scan as one program: another
                        # structure (llama4 interleaves dense and MoE
                        # layers) or other leaf shapes (MiMo-V2's window
                        # and full layers) start a new homogeneous run.
                        flush()
                    run.append(params)
                    run_decoder_idx.append(int(name.split(".")[2]))
                else:
                    flush()
                    kind = {
                        "model.embed_tokens": "embed",
                        "model.norm": "norm",
                        "lm_head": "head",
                    }[name]
                    segments.append((kind, params))
            flush()
        except MemoryError as e:
            # Allocation failure in the stack/cast (outside _load_one's
            # per-layer retry): typed + reported so the shard build fails
            # as a degradable HostOOMError — the producer envelopes it,
            # the serving engine fails only the in-flight waves — never
            # as raw process-killing MemoryError.
            _note_pressure_event("host_oom")
            raise HostOOMError(
                f"host OOM building shard {layer_idxs}: {e}"
            ) from e
        self.load_time += time.perf_counter() - t0
        shard_bytes = sum(
            a.nbytes for _, seg in segments for a in jax.tree.leaves(seg)
        )
        if upload:
            self._count_streamed(shard_bytes)
        if cache is not None and guard is not None:
            # Inserted only AFTER every layer's integrity verification
            # passed (a verify failure raised out of the build above), so
            # cached trees are verified-clean by construction. Consumers
            # treat cached segments as immutable (_place only reads).
            base = self._cache_key_base
            put = cache.put(
                cache_key, segments, nbytes=shard_bytes, guard=guard,
                evict=streamed,
                # this model's other shards: the sweep's cycle (hostcache.put)
                spare=lambda key: key[: len(base)] == base,
            )
            if put and pin:
                self._ask_pinned(cache_key, segments)
        return segments


def _stackable(a: Params, b: Params) -> bool:
    """Whether two layers' trees stack into one scan: the same structure
    and, leaf for leaf, the same shape and dtype."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        x.shape == y.shape and x.dtype == y.dtype for x, y in zip(la, lb)
    )


class _ShardFault:
    """Queue envelope for a producer-side failure: distinguishes "this item
    IS an error" from any conceivable payload, and keeps the original
    exception (with its producer-thread traceback) for chained re-raise on
    the consumer side."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


def _reraise_from_producer(exc: BaseException) -> None:
    """Re-raise a producer-thread exception on the consumer thread as a
    FRESH exception of the same type, chained (``raise ... from``) to the
    original so both threads' tracebacks survive in the report — re-raising
    the stored object itself would splice the consumer's frames onto the
    producer's traceback in place (and mutate it again on every re-raise).
    Exception types whose constructors don't round-trip ``args`` fall back
    to raising the original object."""
    try:
        clone = type(exc)(*exc.args)
    except Exception:  # flscheck: disable=EXC-TAXONOMY: an exception constructor may raise anything; the fallback below re-raises the original object instead
        clone = None
    if clone is None or type(clone) is not type(exc):
        raise exc
    raise clone from exc


@partial(jax.jit, static_argnums=(1,))
def _dequant_tree(tree, np_dtype_name: str):
    """On-device dequantize of every quantized leaf-group: the int8/int4
    bytes crossed the host->HBM link (half / a quarter of the bf16 bytes —
    the transfer is the streaming bottleneck); one fused kernel expands to
    the compute dtype in HBM. (No donation: the narrow buffers cannot alias
    the wider outputs anyway; they free as soon as the caller drops the
    pre-dequant reference.)"""
    target = jnp.dtype(np_dtype_name)

    def one(n):
        if not checkpoint.is_quantized_leaf(n):
            return n
        if checkpoint.quant_kind(n) == "q4":
            # One shared implementation with the host oracle
            # (checkpoint.dequant4_math) so the packing convention cannot
            # desync between the stream and the tests that pin it.
            return checkpoint.dequant4_math(n["q4"], n["s"], jnp).astype(
                target
            )
        q, sc = n["q8"], n["s"]
        # Scale keeps the payload's leading (stack/expert) axes + trailing
        # channel axis; reduced middle axes broadcast. Covers stored [out],
        # stacked [k, out], per-expert [E, out], stacked [k, E, out].
        shape = checkpoint._scale_expand(sc, q.ndim)
        return (q.astype(jnp.float32) * sc.reshape(shape)).astype(target)

    return jax.tree.map(one, tree, is_leaf=checkpoint.is_quantized_leaf)


@partial(jax.jit, static_argnums=(1,))
def _cast_tree(tree, np_dtype_name: str):
    """On-device dtype conversion of every floating leaf to the compute
    dtype — the jitted other half of the zero-host-CPU upload path: the
    stored bytes cross the host->HBM link untouched (fp16/bf16 at half of
    fp32's bytes) and ONE fused convert expands them in HBM. XLA's
    convert rounds to nearest even, bit-identical to the numpy/native
    host cast it replaces. Non-float leaves (per-layer bool flags) pass
    through."""
    target = jnp.dtype(np_dtype_name)

    def one(a):
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != target:
            return a.astype(target)
        return a

    return jax.tree.map(one, tree)


def _needs_device_cast(host, np_dtype) -> bool:
    """True when a HOST segment tree carries floating leaves not already
    in the compute dtype (quantized leaf-groups excluded — their scale is
    consumed by the on-device dequant, which itself emits the target)."""
    target = np.dtype(np_dtype)
    found = False

    def probe(n):
        nonlocal found
        if not checkpoint.is_quantized_leaf(n):
            if _is_floating(n) and n.dtype != target:
                found = True
        return n

    jax.tree.map(probe, host, is_leaf=checkpoint.is_quantized_leaf)
    return found


def _has_quantized(tree) -> bool:
    found = False

    def probe(n):
        nonlocal found
        found = found or checkpoint.is_quantized_leaf(n)
        return n

    jax.tree.map(probe, tree, is_leaf=checkpoint.is_quantized_leaf)
    return found


def _quantized_target(host, target):
    """Adapt a NamedSharding tree (built for the unquantized layout) to a
    host tree that carries {"q8","s"} leaf-groups: the int8 payload takes
    the weight's sharding; its per-output-channel scale takes the channel
    axis of that sharding (plus the stack axis when the loader stacked k
    layers), so the on-device dequant needs no resharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if checkpoint.is_quantized_leaf(host):
        # One shared rank-pad of the (possibly truncated) kernel spec to
        # the payload's rank — both quant kinds slice off this same padded
        # spec, so a future change to the padding convention cannot desync
        # them.
        kind = checkpoint.quant_kind(host)
        q_ndim = np.ndim(host[kind])
        spec = tuple(target.spec)
        spec = spec + (None,) * (q_ndim - len(spec))
        if kind == "q4":
            # int4 payload [.., in/2, out] and group scale [.., in/g, out]
            # have the SAME rank as the unquantized kernel [.., in, out],
            # axis-for-axis: out/expert/stack shards apply verbatim. A
            # Megatron ROW shard (in axis, spec[-2]) slices the packed
            # bytes and the scale rows — exact iff every device's slice is
            # whole groups (in/tp a multiple of INT4_GROUP, which also
            # makes in/2 and in/g divide by tp); anything else would split
            # a quant group across chips, so fail loudly instead.
            in_ax = spec[-2] if q_ndim >= 2 else None
            if in_ax is not None:
                axes = (in_ax,) if isinstance(in_ax, str) else tuple(in_ax)
                tp_size = int(
                    np.prod([target.mesh.shape[a] for a in axes])
                )
                n_groups = host["s"].shape[-2]
                if n_groups % tp_size:
                    raise NotImplementedError(
                        "int4 row shard would split a quantization group "
                        f"across chips: {n_groups} groups of "
                        f"{checkpoint.INT4_GROUP} over tp={tp_size}; pad "
                        "the in dim or use int8 for this kernel"
                    )
            same = NamedSharding(target.mesh, P(*spec))
            return {"q4": same, "s": same}
        s_ndim = np.ndim(host["s"])
        # q8: the scale is LOWER rank than the payload (per-channel, not
        # per-group) — give it the payload's leading axes + its trailing
        # channel axis, the sharding-side mirror of checkpoint._scale_expand.
        s_spec = P(*(spec[: s_ndim - 1] + (spec[-1],))) if s_ndim else P()
        return {"q8": target, "s": NamedSharding(target.mesh, s_spec)}
    if isinstance(host, dict):
        # Some kinds (embed/norm) use ONE sharding for the whole subtree.
        sub = (lambda k: target[k]) if isinstance(target, dict) else (lambda k: target)
        return {k: _quantized_target(host[k], sub(k)) for k in host}
    return target


def _pinned_host_of(device):
    """The sharding of ``device``'s ``pinned_host`` memory, or None: where
    a source's one upload target is a plain chip that lists such a memory,
    the trees it streams every sweep are worth holding there (the chip reads
    it directly; pageable memory is staged first). A placement, a sharding
    or the default device (None, whose uploads stay uncommitted) is not
    such a target."""
    if not isinstance(device, jax.Device):
        return None
    if not any(m.kind == "pinned_host" for m in device.addressable_memories()):
        return None
    return jax.sharding.SingleDeviceSharding(device, memory_kind="pinned_host")


def _on_pinned_host(tree):
    """The chip whose ``pinned_host`` memory holds ``tree``'s arrays (the
    host cache's second form of a streamed tree: all of its arrays or none),
    else None. ``tree`` is a segment's pytree or a whole segment list; only
    the first array's sharding is read, no value is touched."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            if leaf.sharding.memory_kind != "pinned_host":
                return None
            (device,) = leaf.sharding.device_set
            return device
        if isinstance(leaf, np.ndarray):
            return None
    return None


def _place(
    segments: list[tuple[str, Any]], device, np_dtype=None
) -> list[tuple[str, Any]]:
    out = []
    tp = hasattr(device, "segment_target")  # TpPlacement: per-kind shardings
    target_name = np.dtype(np_dtype or np.float32).name
    for kind, p in segments:
        quant = _has_quantized(p)
        # Decided on the HOST tree (before placement): segments whose
        # floats already match the compute dtype skip the cast program
        # entirely, so the fast path pays one cheap probe.
        cast = np_dtype is not None and _needs_device_cast(p, np_dtype)
        if tp:
            target = device.segment_target(kind, p)
            if quant:
                target = _quantized_target(p, target)
            d = jax.device_put(p, target)
        elif (chip := _on_pinned_host(p)) is not None:
            # The tree already sits in memory the chip reads directly: a
            # move between two memories of one chip, no staging copy.
            d = jax.device_put(
                p, jax.sharding.SingleDeviceSharding(chip, memory_kind="device")
            )
        else:
            d = jax.device_put(p, device) if device else jax.device_put(p)
        if quant:
            d = _dequant_tree(d, target_name)
        if cast:
            # On-device cast: the raw stored bytes crossed the link; one
            # fused convert lands them in HBM at the compute dtype
            # (retires the host-side astype from the streaming hot path).
            d = _cast_tree(d, target_name)
        out.append((kind, d))
    return out


def _to_read(idxs, pinned: frozenset, residency, devices) -> tuple[int, ...]:
    """The layer idxs of a shard that its build will read from disk (the
    readahead's targets): every layer but a pin that is seated already —
    shared by both sources so the pin-subtraction rule can't drift
    between them."""
    return tuple(
        i
        for i in idxs
        if i not in pinned or residency.seat_state(i, devices) != "seated"
    )


def _runs(layer_idxs, pinned: frozenset):
    """A shard's layers in order as ``(in_pin_set, idxs)``: each layer of
    the frozen pin set alone, the layers between them as runs (one host
    build and one cache entry each)."""
    for in_set, group in itertools.groupby(layer_idxs, key=pinned.__contains__):
        if in_set:
            yield from ((True, (i,)) for i in group)
        else:
            yield False, tuple(group)


def _split_parts(
    loader: _HostShardLoader,
    layer_idxs: tuple[int, ...],
    pinned: frozenset,
    residency=None,
    devices: Sequence = (None,),
) -> list[tuple[str, int, Any]]:
    """One shard's build, partial-residency aware: ``(kind, idx, host
    segments)`` parts in layer order. ``("stream", -1, host)`` is a run of
    unpinned layers; a layer of the source's frozen pin set is always a
    part of its own, so the segment structure the consumer sees never
    depends on what is seated yet: ``("pin", idx, None)`` once it is
    resident on every one of ``devices`` (nothing read, nothing
    uploaded), ``("seat", idx, host)`` in the sweep that finds it planned
    and not yet resident (read and verified here like any streamed layer;
    ``_assemble_parts`` keeps what it places), ``("stream", idx, host)``
    after a demotion. A seat whose build fails demotes the layer and
    raises the stream path's own typed error. With no pins this is
    exactly one ("stream", -1, build_host_shard(idxs)) part — the
    pre-residency fast path, byte for byte."""
    parts: list[tuple[str, int, Any]] = []
    for in_set, idxs in _runs(layer_idxs, pinned):
        if not in_set:
            parts.append(("stream", -1, loader.build_host_shard(idxs)))
            continue
        (i,) = idxs
        state = residency.seat_state(i, devices)
        if state == "seated":
            parts.append(("pin", i, None))
            continue
        try:
            # A demoted layer crosses the link every sweep like any other.
            host = loader.build_host_shard(idxs, streamed=state != "unseated")
        except Exception:
            residency.demote(i)
            raise
        parts.append(("seat" if state == "unseated" else "stream", i, host))
    return parts


def _assemble_parts(parts, device, np_dtype, residency) -> list[tuple[str, Any]]:
    """Place the streamed runs and merge the pinned layers' resident
    segments back at their positions — the full shard's segment list in
    layer order, exactly what an unpinned ``_place(build_host_shard(...))``
    would have produced (same trees, same order; the pinned ones just
    weren't re-read or re-uploaded). A ``seat`` part is placed like a
    streamed one and then kept by the tier: the sweep's own upload is the
    pin's only one. A placement that fails (no room) demotes the layer."""
    out: list[tuple[str, Any]] = []
    for kind, idx, host in parts:
        if kind == "stream":
            out.extend(_place(host, device, np_dtype=np_dtype))
            continue
        placed = residency.seated(idx, device)
        if placed is None:  # a seat, or a retried put whose seat landed
            try:
                placed = _place(host, device, np_dtype=np_dtype)
            except Exception:
                residency.demote(idx)
                raise
            placed = residency.seat(idx, device, host, placed)
        out.extend(placed)
    return out


class _UploadWatcher:
    """Completion thread of one ``ShardWeightSource``: times each shard's
    upload to where it COMPLETES. ``jax.device_put`` returns at the
    enqueue, so the producer hands the placed arrays over at dispatch and
    this thread blocks until they are ready: the ``upload`` span runs from
    the dispatch to that return. References are dropped at once; an array
    deleted before its wait (a consumer already done with it) is a counted
    miss, not an error."""

    def __init__(self):
        self._q: SimpleQueue = SimpleQueue()
        self._lock = threading.Lock()
        # Completed uploads, newest last. One pass has a few dozen; the
        # bound only keeps a long-lived source (a decode stream of many
        # passes, which no account reads) from growing with its life.
        self.intervals: deque = deque(maxlen=4096)  # guarded by: _lock
        self.misses = 0  # guarded by: _lock
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="fls-upload-watch", daemon=True
        )
        self._thread.start()

    def watch(self, arrays, t_dispatch: float, attrs: dict) -> None:
        if not self._closed:  # a closed watcher must hold no array
            self._q.put((arrays, t_dispatch, attrs))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            arrays, t_dispatch, attrs = item
            del item
            missed = False
            with obs_trace.timed("upload", cat="stream", **attrs) as sp:
                sp.t0 = t_dispatch  # the link may carry from the dispatch on
                try:
                    jax.block_until_ready(arrays)
                except RuntimeError:  # deleted under us: nothing to time
                    missed = True
                    sp.drop()
                del arrays
            with self._lock:
                if missed:
                    self.misses += 1
                else:
                    self.intervals.append(
                        (sp.t0, sp.t0 + sp.dur_s, attrs.get("shard_idx", -1))
                    )

    def close(self, timeout_s: float = 10.0) -> None:
        """Finish the queued waits and retire the thread (bounded: a wait
        on a wedged device is abandoned with the daemon thread)."""
        self._closed = True
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join(timeout=timeout_s)
        while True:  # what a dead or abandoned thread left: hold no array
            try:
                self._q.get_nowait()
            except Empty:  # the thread may take the last item under us
                break

    def snapshot(self) -> tuple[list[tuple[float, float, int]], int]:
        """The completed uploads' ``(t_dispatch, t_done, shard_idx)`` and the
        count of misses."""
        with self._lock:
            return list(self.intervals), self.misses


def lead_uploads(queued_s: float, upload_s: Sequence[float], cap: int) -> int:
    """How many of a streamed run's first uploads go out together once the
    seated head before the run is dispatched: the first always; each
    further one only while the device work queued ahead of them
    (``queued_s``) is expected to outlast all of them (``upload_s``: each
    one's seconds, in order: they cross the link one after another); never
    more than ``cap``."""
    lead, total = 1, upload_s[0] if upload_s else 0.0
    for seconds in upload_s[1:cap]:
        total += seconds
        if total > queued_s:
            break
        lead += 1
    return lead


class ShardWeightSource:
    """Loads shard weights disk -> host -> HBM, optionally prefetching ahead.

    One shard's payload is a dict: ``{"segments": [(kind, params), ...]}``
    where decoder runs are pre-stacked [k, ...] pytrees ready for scan. With
    ``prefetch_depth >= 1`` a daemon thread stays ``depth`` shards ahead of
    compute, so the host->HBM transfer of shard t+1 overlaps the device
    compute of shard t (the reference serializes these,
    ``/root/reference/utils.py:228-233``).

    One rule releases the uploads, held in one place (``_gate``). The
    source has ``prefetch_depth`` + 1 places: a build takes one once its
    host side is done (the cache hit, the ``pinned_host`` tree), just before
    its ``device_put``, and the consumer gives its shard's place back by
    letting go of the shard: ``dispatched()`` once the shard's last block is
    enqueued, or, having said nothing, asking for the next shard (a build
    that fails for good gives its place back itself: the consumer takes a
    fault there and holds nothing). Build ``j``'s ``device_put`` goes out
    when a place is free and, for an upload of the run ``h .. r-1`` behind a
    seated head (shards ``0 .. h-1`` upload nothing and hold a decoder
    layer) where the consumer passed ``lead`` (the executor's one-chip
    scoring pass, whose blocks stay on the chip), once the consumer has let
    go of shard ``max(h - 1, j - lead)``; ``lead`` is planned as it lets go
    of shard ``h - 1`` (``lead_uploads``: one, and a second or third only
    while the head's device work still queued is expected to outlast them,
    read off the previous sweep's record: ``head_queued_s``, and its link
    rate). So an upload goes out BEHIND the steps just dispatched and runs
    beside them (on the chip a program launched after a transfer's enqueue
    waits for it; launched before, both run side by side), and the link
    starts at the head's end. Every other consumer (serving's cycling
    source, the pipeline, KV decode, DP's broadcast) and a plan with no
    seated head have the places alone; a consumer that never says
    "dispatched" keeps the same lead and the same bytes in HBM, with no
    order promised.

    A shard is handed to the consumer only once the newest upload to its
    device has ARRIVED (``_await_upload``, inside the consumer's ``next()``;
    ``link_wait_s``), and the run's first shard only once the lead's uploads
    are all enqueued. "Dispatched" on the host is not "issued" on the chip:
    a launch, when the runtime issues it, waits for every transfer enqueued
    by then, and while it waits the launches behind it are not issued
    either, so they also wait for the uploads enqueued MEANWHILE. Steps
    dispatched under an upload still in flight would therefore wait for the
    next upload too, the one their own "dispatched" lets out (read on the
    chip: every second streamed shard took an upload and a half longer).
    Dispatched with the link idle they are issued at once, and the upload
    their signal lets out runs beside them: a streamed shard costs the
    larger of its upload and its steps.

    At a shard's end the consumer asks one question, ``end_wait()``: wait
    for the shard now, let the wait lag one shard, or skip it (a head shard
    before the head's last, whose wait covers it).

    ``cycle=True`` loops the shard list endlessly instead of stopping after
    one pass — the online serving loop's weight stream, where the number of
    full-model sweeps is open-ended (requests keep arriving) and a
    per-sweep source would cold-start the prefetch pipeline at every
    shard-0 boundary. The consumer takes exactly ``len(shards)`` items per
    sweep and MUST ``close()`` the source to end the stream.
    """

    def __init__(
        self,
        model_path: str,
        layer_names: Sequence[str],
        shards: Sequence[tuple[int, ...]],
        np_dtype,
        device=None,
        prefetch_depth: int = 1,
        tied_embeddings: bool = False,
        devices: Sequence | None = None,
        layer_sliding=None,
        layer_rope=None,
        cycle: bool = False,
        layer_linear=None,
        layer_index: bool = False,
        retry_policy: RetryPolicy | None = None,
        injector: FaultInjector | None = None,
        retry_recorder=None,
        integrity_recorder=None,
        verify_weights: bool = True,
        host_cache=None,
        readahead_threads: int = 2,
        residency=None,
        sweep_id: int = 0,
        first_shard_idx: int = 0,
        lead: dict | None = None,
    ):
        # lead: the previous sweep's record ({} where there is none) where the
        # consumer lets the link start at a seated head (the class
        # docstring); None: the places alone.
        # residency: a runtime.residency.DeviceResidencyTier (or None) —
        # pinned layers are subtracted from every shard build (their bytes
        # never cross the link) and merged back as resident segments at
        # placement. The pin set is FROZEN here so this source's segment
        # structure can never change mid-life (a serving wave's prefill
        # and decode must agree on it).
        # sweep_id / first_shard_idx: the pass this source feeds and the
        # global index of its first shard, for the producer's spans (0 for
        # a cycling source, whose consumer numbers its own sweeps).
        self.sweep_id = sweep_id
        self._first_shard_idx = first_shard_idx
        self.shards = list(shards)
        # Either one device for every shard, or (pipeline mode) one target
        # device per shard — shard t's weights upload straight to its stage's
        # chip while stage t-1 computes elsewhere.
        if devices is not None:
            if len(devices) != len(self.shards):
                raise ValueError("devices must align 1:1 with shards")
            self.shard_devices = list(devices)
        else:
            self.shard_devices = [device] * len(self.shards)
        self.cycle = cycle
        self._retry = retry_policy or RetryPolicy()
        self._injector = injector
        self._recorder = retry_recorder
        self._stop = threading.Event()
        self._loader = _HostShardLoader(
            model_path, layer_names, np_dtype, tied_embeddings, layer_sliding,
            layer_rope, layer_linear, layer_index, retry_policy=self._retry,
            injector=injector,
            retry_recorder=retry_recorder, retry_abort=self._stop.is_set,
            integrity=integrity_recorder, verify_weights=verify_weights,
            host_cache=host_cache, readahead_threads=readahead_threads,
            # One target chip for every shard: its streamed trees may live
            # in that chip's pinned_host memory. Per-shard devices (MP) and
            # placements keep NumPy trees.
            pinned_host=(
                _pinned_host_of(device)
                if devices is None and host_cache is not None
                else None
            ),
        )
        self._residency = residency
        # Nothing is loaded here: a planned layer that is not resident yet
        # is seated from this source's own stream (_split_parts), on the
        # producer's thread, by the read, check and upload the sweep makes
        # of it anyway.
        self._pinned_idxs: frozenset = (
            residency.frozen_pinned(self.shards)
            if residency is not None
            else frozenset()
        )
        self.pin_hits = 0  # pinned layers this source merged, not uploaded
        # Decoder-layer visits served from a seat / from an upload (a looped
        # plan visits a layer several times a pass).
        self.visits_pinned = self.visits_streamed = 0
        self.produce_time = 0.0  # set BEFORE the producer thread starts
        # The producer's side of the sweep's account (see account()).
        self.upload_dispatch_s = 0.0
        self.producer_blocked_s = 0.0
        self.upload_bytes = 0
        self.upload_pinned_bytes = 0  # of upload_bytes, from pinned_host
        # shard_idx -> (shard_load_s, upload_dispatch_s, upload_ordered): the
        # producer's seconds by shard and whether its device_put went out on
        # a place dispatched() returned, for a slow sweep's table
        # (shard_table()); the second also dates the enqueue's END.
        self._produced: dict[int, tuple[float, float, int]] = {}
        # Uploads whose device_put went out on a place that dispatched()
        # returned (against the account's ``uploads``).
        self.uploads_ordered = 0
        # The newest upload, (device, arrays), until a consumer has seen it
        # arrive, and the consumer's seconds waiting for that (_await_upload).
        self._in_flight: tuple | None = None
        self.link_wait_s = 0.0
        # The completion thread exists where an account reads it: a
        # one-pass source, closed when its sweep's record is written. A
        # cycling source (the serve engine's, which keeps no account and
        # lives as long as the engine) gets none: no thread, no
        # per-upload state, no second join on the watchdog's recovery path.
        self._watcher = None if cycle else _UploadWatcher()
        self._q: Queue = Queue(maxsize=max(1, prefetch_depth))
        # The release rule (the class docstring). Plan facts: the places
        # beyond the one in hand, and the run of uploads behind a seated head
        # (None: no lead). Under _cv: the free places, oldest first, each
        # True where dispatched() returned it; the shard the consumer let go
        # of last and the one the producer put last; the lead, planned as
        # the head's last shard is let go of. prefetch_depth 0 builds in the
        # consumer's next(): no thread, no places.
        self._depth = max(0, prefetch_depth)
        self._lead_seen = lead or {}
        self._run: range | None = None
        if lead is not None and prefetch_depth >= 1 and devices is None and not cycle:
            self._run = self._run_behind_head()
        self._cv = threading.Condition()
        self._places: deque = deque([False] * (self._depth + 1))  # guarded by: _cv
        self._released = self._passed = -1  # guarded by: _cv
        self._lead: int | None = None  # guarded by: _cv
        self.uploads_led = self.lead_planned = 0
        # For end_wait: the shard the consumer holds (counted from the
        # first: a one-pass source's position) and the positions whose
        # build uploads (the producer adds one before its put).
        self._held: int | None = None
        self._uploaded: set[int] = set()
        self._close_lock = threading.Lock()  # close() may race abort()/close()
        self._thread: threading.Thread | None = None
        if prefetch_depth >= 1:
            self._thread = threading.Thread(target=self._producer, daemon=True)
            self._thread.start()

    def abort(self) -> None:
        """Non-blocking close for the recovery paths (the serving engine's
        stall watchdog fires this from ITS thread): set stop and drain the
        queue so both the producer's pending put and the consumer's pending
        get unblock promptly — without joining the (possibly wedged)
        producer thread here. The owner still calls close() afterwards."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except Empty:
                break

    def close(self, join_timeout_s: float = 10.0) -> None:
        """Unblock and retire the prefetch thread; drop any queued shards so
        their HBM buffers are released even if iteration was abandoned.
        Idempotent and thread-safe (recovery may close concurrently with
        the watchdog's abort).

        The join is BOUNDED: a producer wedged in an uninterruptible I/O
        syscall (hung NFS hard mount) can never be joined, and the serving
        engine's recovery path runs through here — blocking forever would
        hang exactly the futures the watchdog exists to unhang. Past the
        bound the daemon thread is abandoned: _put discards everything once
        stop is set and retries abort on the stop flag, so it exits on its
        own the moment the syscall returns (or dies with the process)."""
        self._stop.set()
        with self._close_lock:
            deadline = time.monotonic() + join_timeout_s
            if self._thread is not None:
                while self._thread.is_alive():
                    if time.monotonic() >= deadline:
                        break  # abandoned, self-terminates via _stop
                    try:
                        self._q.get_nowait()
                    except Empty:
                        self._thread.join(timeout=0.1)
                self._thread = None
            while not self._q.empty():
                try:
                    self._q.get_nowait()
                except Empty:
                    break
            self._in_flight = None  # a closed source holds no array
            # Retire the loader's native readahead pool promptly — a source
            # is created per executor call and sits in a reference cycle
            # (producer thread target holds self), so GC alone would strand
            # thread pools.
            self._loader.close()
            # The producer is gone, so nothing more is handed over: the
            # completion thread finishes its queued waits and exits, and
            # account() reads final numbers. One deadline bounds both joins.
            if self._watcher is not None:
                self._watcher.close(max(0.0, deadline - time.monotonic()))

    def shard_table(
        self, clock: "SweepClock", t_end: float, intervals=None
    ) -> list[dict]:
        """The consumer's per-shard stamps (``clock.shards``) lined up with
        this source's uploads (``intervals``: the watcher's snapshot, taken
        here unless given) and builds: ``_shard_table``'s rows. Read after
        ``close()``."""
        if intervals is None:
            intervals = self._watcher.snapshot()[0] if self._watcher else []
        # An upload counts as enqueued where its device_put call RETURNED
        # (a run of layers goes in leaf by leaf, for milliseconds: a block
        # launched meanwhile queues behind the leaves already in, not behind
        # the upload). The link's own interval still opens at the call.
        uploads = {
            idx: (a + self._produced[idx][1] if idx in self._produced else a, b)
            for a, b, idx in intervals
        }
        return _shard_table(clock, t_end, uploads, self._produced)

    def account(
        self, t_lo: float, t_hi: float, clock: "SweepClock | None" = None
    ) -> dict:
        """The producer's side of a sweep's account over ``[t_lo, t_hi]``
        (``perf_counter``): host build, upload dispatch and blocked-on-
        queue seconds, and the link as the completion thread saw it —
        ``upload_busy_s`` is the UNION of the ``upload`` intervals inside
        the window, ``upload_bytes`` the host bytes handed to
        ``device_put`` for streamed parts (the streamed-bytes counter's
        delta; pinned layers upload nothing). A one-pass source (one that
        times its uploads) also lines the consumer's per-shard stamps
        (``clock.shards``) up with them: why the device stood idle between shards
        (``drained_s``, ``own_upload_wait_s``, ``behind_upload_s``).
        ``uploads_ordered`` counts the uploads enqueued on a place that
        ``dispatched()`` returned; ``lead_planned`` / ``uploads_led`` what
        the seated head's release allowed and let out, and
        ``head_queued_s`` (a sweep that released) what its last shard's wait
        read. Read after ``close()``."""
        intervals, misses = (
            self._watcher.snapshot() if self._watcher is not None else ([], 0)
        )
        idle = {}
        if self._watcher is not None and clock is not None:
            shards = clock.shards
            table = self.shard_table(clock, t_hi, intervals)
            idle = {
                "drained_s": sum(r["drained_s"] for r in table),
                "drained_shards": sum(
                    a.t_ready is not None and b.t_launch is not None
                    for a, b in zip(shards, shards[1:])
                ),
                "own_upload_wait_s": sum(r["own_upload_wait_s"] for r in table),
                "behind_upload_s": sum(r["behind_upload_s"] for r in table),
                "launches_behind_upload": sum(
                    r["behind_upload_s"] > 1e-3 for r in table
                ),
            }
        return {
            **idle,
            "host_build_s": self._loader.build_time,
            "upload_dispatch_s": self.upload_dispatch_s,
            "producer_blocked_s": self.producer_blocked_s,
            "link_wait_s": self.link_wait_s,
            "upload_busy_s": union_seconds(
                [(a, b) for a, b, _ in intervals], t_lo, t_hi
            ),
            "upload_bytes": self.upload_bytes,
            "upload_pinned_bytes": self.upload_pinned_bytes,
            "uploads": len(intervals),
            "uploads_ordered": self.uploads_ordered,
            "uploads_led": self.uploads_led,
            "lead_planned": self.lead_planned,
            **self._head_queued(clock),
            "upload_misses": misses,
            "pinned_bytes": (
                self._residency.max_pinned_device_bytes()
                if self._residency is not None
                else 0
            ),
            "pin_hits": self.pin_hits,
            "visits_pinned": self.visits_pinned,
            "visits_streamed": self.visits_streamed,
        }

    def _head_queued(self, clock: "SweepClock | None") -> dict:
        """``head_queued_s``: the wait at the seated head's last shard,
        which begins where its "dispatched" released the lead: the head's
        device work still queued then (what the next sweep's lead is
        planned on). Empty where nothing was released."""
        if not self.lead_planned or clock is None:
            return {}
        last = self._first_shard_idx + self._run.start - 1
        for s in clock.shards:
            if s.shard_idx == last and s.t_ready is not None:
                return {"head_queued_s": s.t_ready - s.t_wait}
        return {}

    @property
    def load_time(self) -> float:
        return self._loader.load_time

    @property
    def bytes_loaded(self) -> int:
        return self._loader.bytes_loaded

    @property
    def host_casts(self) -> int:
        return self._loader.host_casts

    def _to_read(self, shard_i: int) -> tuple[int, ...]:
        return _to_read(
            self.shards[shard_i], self._pinned_idxs, self._residency,
            (self.shard_devices[shard_i],),
        )

    def _build_shard(
        self, layer_idxs: tuple[int, ...], device, shard_i: int = 0
    ) -> list[tuple[str, Any]]:
        from flexible_llm_sharding_tpu.runtime.hostcache import _tree_nbytes

        # produce_time covers the producer's WHOLE per-shard wall — host
        # file->numpy load (load_time counts just that part) plus the
        # device placement dispatch — the denominator of the stats line's
        # overlap_efficiency (source_wait_s over
        # produce_wall_s compares like with like; load_time alone
        # under-counts what overlap must hide on a slow host->HBM link).
        # Host time around asynchronous dispatch, not link or device time:
        # the sweep account's upload_busy_s is the link's.
        ids = self._span_ids(shard_i)
        attrs = dict(
            ids, first=layer_idxs[0] if layer_idxs else -1, n=len(layer_idxs)
        )
        self._loader.trace_ids = ids
        with obs_trace.timed("shard_produce", cat="stream", **attrs) as produce:
            bytes_before = self._loader.bytes_loaded
            build_before = self._loader.build_time
            parts = _split_parts(
                self._loader, layer_idxs, self._pinned_idxs, self._residency,
                (device,),
            )
            nbytes = self._loader.bytes_loaded - bytes_before
            load_s = self._loader.build_time - build_before
            if any(kind != "pin" for kind, _, _ in parts):
                # Said before the put below seats anything (end_wait).
                self._uploaded.add(shard_i)
            # Count the sweep's saved link bytes ONCE per build (the put
            # below may retry; retries must not double-count).
            pinned_nbytes = 0
            seated = set()
            for kind, idx, host in parts:
                if kind == "pin":
                    self._residency.note_skip(idx)
                    self.pin_hits += 1
                    seated.add(idx)
                elif _on_pinned_host(host) is not None:
                    pinned_nbytes += _tree_nbytes(host)
            n_names = len(self._loader.layer_names)
            pinned = decoder_visits([i for i in layer_idxs if i in seated], n_names)
            self.visits_pinned += pinned
            self.visits_streamed += decoder_visits(layer_idxs, n_names) - pinned

            # The host->device put retries under the same policy as the
            # reads: a transfer that surfaces OSError/TimeoutError is
            # treated like a flaky filesystem read. The 'device_put' fault
            # site sits inside the retried region.
            def put():
                if self._injector is not None:
                    # link_throttle stalls (never errors) — a saturated
                    # host->HBM link is slowness the pressure monitor's
                    # link-rate signal sees, not a fault to retry.
                    self._injector.fire("link_throttle", detail=str(layer_idxs))
                    self._injector.fire("device_put", detail=str(layer_idxs))
                return _assemble_parts(
                    parts, device, self._loader.np_dtype, self._residency
                )

            # The host side is done; only the device_put waits for its
            # gate, once a shard and outside the retried region.
            blocked_before = self.producer_blocked_s
            behind_head = bool(nbytes) and self._run is not None and shard_i in self._run
            ordered = led = False
            if self._depth:

                def take(timeout):  # the oldest free place, once the gate opens
                    if self._cv.wait_for(lambda: self._gate(shard_i, behind_head), timeout):
                        return self._places.popleft()
                    return None

                with self._cv:
                    ordered = self._wait(take, shard_i)
                    led = behind_head and shard_i < self._run.start + self._lead
            # upload_dispatch is the CALL (device_put returns at the
            # enqueue), not the transfer: the completion thread's upload
            # span, opened at this dispatch, ends where the bytes arrived.
            try:
                with obs_trace.timed(
                    "upload_dispatch", cat="stream", **attrs
                ) as dispatch:
                    out = retry_call(
                        put,
                        policy=self._retry,
                        label="device_put",
                        recorder=self._recorder,
                        wrap=ShardLoadError,
                        abort=self._stop.is_set,
                    )
            except BaseException:
                # Nothing was built: the consumer takes a fault in this
                # shard's place and holds nothing, so the place comes back here.
                self._release(failed=True)
                raise
            if nbytes:
                arrays = [seg for _, seg in out]
                self._in_flight = (device, arrays)
                if self._watcher is not None:
                    self._watcher.watch(arrays, dispatch.t0, dict(attrs, bytes=nbytes))
        self.upload_dispatch_s += dispatch.dur_s
        self.uploads_led += led
        ordered = int(bool(nbytes and ordered))
        self._produced[ids["shard_idx"]] = (load_s, dispatch.dur_s, ordered)
        self.upload_bytes += nbytes
        self.upload_pinned_bytes += pinned_nbytes
        self.uploads_ordered += ordered
        # The wait at the gate is the consumer's pace, not the producer's work.
        self.produce_time += produce.dur_s - (
            self.producer_blocked_s - blocked_before
        )
        return out

    def _span_ids(self, shard_i: int) -> dict:
        return {
            "sweep_id": self.sweep_id,
            "shard_idx": self._first_shard_idx + shard_i,
        }

    # -- the release rule (the class docstring) ----------------------------
    def dispatched(self) -> None:
        """Consumer: the last block of the shard it holds is enqueued on the
        device. Lets go of that shard, so the producer's next ``device_put``
        lands behind the shard's steps (and before the consumer's wait at the
        shard's end, which the upload then runs beside). Once a shard; a
        second call, or one with no shard held, does nothing."""
        self._release(dispatched=True)

    def end_wait(self) -> str:
        """Consumer, after ``dispatched()``: what to do about its wait for
        the shard it holds. "skip": a shard of the seated head before the
        head's last, whose wait covers it (the run's uploads wait for that
        last shard). "lag": the wait may come one shard later (blocked on at
        the next shard's dispatch, so the device has this shard queued while
        the host takes and dispatches the next), which is only where no
        upload can go out behind launches queued meanwhile and the host
        still holds streamed buffers back: the shard uploaded nothing, it is
        not the last, and neither the build that this ``dispatched()`` lets
        out nor the one the next shard's lets out (``prefetch_depth`` + 1
        and + 2 ahead) uploads anything: every layer of theirs seated, or
        past the end of the plan. "wait" otherwise: the head's last shard,
        and every shard of a cycling source."""
        i, n = self._held, len(self.shards)
        if self.cycle or i is None or i in self._uploaded or i + 1 >= n:
            return "wait"
        if self._run is not None and i < self._run.start:
            return "skip" if i < self._run.start - 1 else "wait"
        # A build that uploads is in _uploaded before its put seats a layer,
        # and reads an unseated one until then: no race with the producer.
        ahead = (i + self._depth + 1, i + self._depth + 2)
        if any(j in self._uploaded or self._to_read(j) for j in ahead if j < n):
            return "wait"
        return "lag"

    def _gate(self, j: int, behind_head: bool) -> bool:
        """Under ``_cv``: build ``j``'s ``device_put`` may go out. A place is
        free and, for an upload of the run ``h .. r-1`` behind the seated
        head (``behind_head``), the consumer has let go of shard ``max(h - 1,
        j - lead)`` (the lead is planned as it lets go of ``h - 1``)."""
        # flscheck: holds=_cv: asked only inside the producer's wait_for
        if not self._places:
            return False
        return not behind_head or (
            self._lead is not None and self._released >= j - self._lead
        )

    def _release(self, dispatched: bool = False, failed: bool = False) -> None:
        """A place comes back: the consumer lets go of the shard it holds
        (once; letting go of the seated head's last shard plans the lead),
        or (``failed``) the producer gives back the place of a build that
        failed for good."""
        if not self._depth:
            return
        with self._cv:
            if not failed:
                if self._held is None or self._released >= self._held:
                    return
                self._released = self._held
                if self._run is not None and self._released == self._run.start - 1:
                    self._lead = self.lead_planned = self._plan_lead()
            self._places.append(dispatched)
            self._cv.notify_all()

    def _run_behind_head(self) -> range | None:
        """Shards ``h .. r-1`` that each upload, behind a head ``0 .. h-1``
        that uploads nothing and holds a decoder layer; None where the plan
        opens otherwise."""
        reads = [bool(self._to_read(j)) for j in range(len(self.shards))]
        if True not in reads:
            return None
        h = reads.index(True)
        n_names = len(self._loader.layer_names)
        if not any(decoder_visits(self.shards[j], n_names) for j in range(h)):
            return None
        r = h
        while r < len(reads) and reads[r]:
            r += 1
        return range(h, r)

    def _plan_lead(self) -> int:
        """``lead_uploads`` over the run's first builds: the device work
        still queued at the previous sweep's release (``head_queued_s``)
        against each build's bytes at the link rate that sweep measured;
        one where the record has neither."""
        seen = self._lead_seen
        queued, busy = seen.get("head_queued_s"), seen.get("upload_busy_s")
        if queued is None or not busy or not seen.get("upload_bytes"):
            return 1
        from flexible_llm_sharding_tpu.runtime.residency import layer_stream_bytes

        rate, loader = seen["upload_bytes"] / busy, self._loader
        cap = min(self._depth + 1, len(self._run))
        seconds = [
            sum(layer_stream_bytes(
                loader.model_path,
                [loader.layer_names[i] for i in self._to_read(j)],
                loader.tied,
            ).values()) / rate
            for j in self._run[:cap]
        ]
        return lead_uploads(queued, seconds, cap)

    def _wait(self, attempt: Callable[[float], Any], shard_i: int | None = None):
        """Every blocking wait of the source: ``attempt(timeout)`` until it
        returns something other than None, which is returned (``timeout``
        is 0 the first time, 0.2 s after that). ``close()`` and ``abort()``
        end it within 0.2 s: it raises ``SourceClosed``. A wait of the
        producer's (``shard_i`` given) that does not pass at once is its
        ``producer_blocked`` span and adds to ``producer_blocked_s``."""
        if self._stop.is_set():
            raise SourceClosed("ShardWeightSource closed while streaming")
        got = attempt(0)
        if got is None:
            with (
                obs_trace.timed(
                    "producer_blocked", cat="stream", **self._span_ids(shard_i)
                )
                if shard_i is not None
                else contextlib.nullcontext()
            ) as blocked:
                while got is None and not self._stop.is_set():
                    got = attempt(0.2)
            if shard_i is not None:
                self.producer_blocked_s += blocked.dur_s
            if got is None:
                raise SourceClosed("ShardWeightSource closed while streaming")
        return got

    def _await_upload(self, device) -> None:
        """Consumer, a shard for ``device`` in hand: wait until the newest
        upload to that device has arrived, so that the shard's steps are
        issued the moment they are dispatched (see the class docstring). An
        upload to another chip (a pipeline's next stage) holds nothing back
        here."""
        in_flight = self._in_flight
        if in_flight is None or in_flight[0] is not device:
            return
        with obs_trace.timed(
            "link_wait", cat="stream", sweep_id=self.sweep_id
        ) as wait:
            try:
                jax.block_until_ready(in_flight[1])
            except RuntimeError:  # deleted under us: nothing left to wait for
                pass
        self.link_wait_s += wait.dur_s
        if self._in_flight is in_flight:  # seen to arrive: hold it no longer
            self._in_flight = None

    # -- prefetch thread ---------------------------------------------------
    def _put(self, item, shard_i: int = 0) -> bool:
        """Producer: queue a build (or its fault), waiting while the queue is
        full; False once the source is closed. Stop is checked BEFORE every
        attempt, including the first: close()/abort() may fire between
        building the item and queueing it, and a put landing in the
        just-drained queue would strand a shard's HBM buffers (or an error
        nobody consumes) while close() joins this thread."""
        with self._cv:
            self._passed = shard_i
            self._cv.notify_all()

        def put(timeout):
            try:
                self._q.put(item, timeout=timeout)
                return True
            except Full:
                return None

        try:
            return self._wait(put, shard_i)
        except SourceClosed:
            return False

    def _build_streamed_first(self) -> None:
        """Producer, before the first shard of a sweep that will seat layers
        of the residency tier (a process's first, a re-seat after a
        release): build the runs that stay streamed, for the host cache
        alone. The sweep's order would reach them last, behind the seconds
        of reading and verifying what the tier keeps, and the pinned_host
        copies the cache then makes of them (hostcache.HostShardCache.pin:
        seconds a layer, on its own thread) would run under the sweeps that
        follow. Built first, they are copied while this sweep reads the
        seats, and their own builds further on are cache hits. The work of
        the sweep is the same, in another order. The pass ends where the
        cache starts to evict (what it built would only push itself out)
        and on an error: the build in the sweep's order meets that again
        and reports it."""
        if self._loader._pinned_host is None or not any(
            # one target chip (a pinned_host loader has no other kind)
            self._residency.seat_state(i, self.shard_devices[:1]) == "unseated"
            for i in self._pinned_idxs
        ):
            return
        self._loader.trace_ids = {"sweep_id": self.sweep_id}
        cache = self._loader._host_cache
        full = cache.evictions + cache.scan_refusals
        built = set()  # a looped plan lists a run once a step
        for idxs in self.shards:
            for in_set, run in _runs(idxs, self._pinned_idxs):
                if self._stop.is_set() or cache.evictions + cache.scan_refusals != full:
                    return
                if not in_set and run not in built:
                    built.add(run)
                    try:
                        self._loader.build_host_shard(run, upload=False)
                    except Exception:  # flscheck: disable=EXC-TAXONOMY: whatever a build raises, the sweep's own build of the run raises again at the shard's position, where the consumer takes it
                        return

    def _producer(self):
        self._build_streamed_first()
        while True:
            for i, (idxs, dev) in enumerate(
                zip(self.shards, self.shard_devices)
            ):
                if self._stop.is_set():
                    return
                try:
                    # Readahead the next shard's files (pinned layers never
                    # re-read, so they are skipped); in cycle mode the
                    # sweep wraps, so the last shard warms shard 0 again.
                    nxt = i + 1
                    if nxt < len(self.shards):
                        self._loader.warm(self._to_read(nxt))
                    elif self.cycle:
                        self._loader.warm(self._to_read(0))
                    item = self._build_shard(idxs, dev, i)
                except Exception as e:  # flscheck: disable=EXC-TAXONOMY: EVERY producer error must travel to the consumer as a _ShardFault envelope — narrowing would let an unexpected type kill the thread and hang the consumer's get
                    # Surface to the consumer at this shard's position, but
                    # keep the thread ALIVE: retries are already exhausted
                    # inside _build_shard, yet one persistently bad shard
                    # must not end the stream for good — the serving engine
                    # fails only the in-flight wave and keeps consuming
                    # (offline consumers raise and close(), which stops this
                    # loop via _stop on the next iteration).
                    if not self._put(_ShardFault(e), i):
                        return
                    continue
                if not self._put(item, i):
                    return
            if not self.cycle:
                return

    def _get(self):
        """Consumer: the next queued item. A closed source raises
        ``SourceClosed``: a consumer must never hang forever on a queue whose
        producer died or whose source a watchdog aborted."""

        def get(timeout):
            try:
                return self._q.get(timeout=timeout)
            except Empty:
                return None

        return self._wait(get)

    def __iter__(self):
        if self._thread is None:
            while True:
                for i, (idxs, dev) in enumerate(
                    zip(self.shards, self.shard_devices)
                ):
                    if self._stop.is_set():
                        return
                    if i + 1 < len(self.shards):
                        self._loader.warm(self._to_read(i + 1))
                    item = self._build_shard(idxs, dev, i)
                    self._held = i
                    yield idxs, item
                if not self.cycle:
                    return
        else:
            taken = itertools.count()
            while True:
                for i, (idxs, dev) in enumerate(
                    zip(self.shards, self.shard_devices)
                ):
                    # A consumer that did not say "dispatched" for the shard
                    # it held lets it go by asking for the next.
                    self._release()
                    item = self._get()
                    if isinstance(item, _ShardFault):
                        _reraise_from_producer(item.error)
                    if self._run is not None and i == self._run.start:
                        # The lead's uploads are all enqueued (or failed), so
                        # the arrival waited for next is the newest of them.
                        with self._cv:
                            last = i + self._lead - 1
                            self._wait(lambda t: self._cv.wait_for(
                                lambda: self._passed >= last, t
                            ) or None)
                    self._await_upload(dev)
                    self._held = next(taken)
                    yield idxs, item
                if not self.cycle:
                    return


class BroadcastShardSource:
    """DP weight sharing: ONE disk read + cast per shard, broadcast to every
    DP chip.

    Replaces the reference's ``DeviceManager`` layer cache
    (``/root/reference/utils.py:31-75``): its request queue, condition-variable
    handoff, and per-layer device refcount/eviction protocol collapse into a
    single producer thread that loads each shard once and feeds one bounded
    queue per chip; a consumer drops its reference after use and XLA's
    allocator reclaims the HBM (no eviction bookkeeping).

    ``rounds`` repeats the shard sequence (the executor's ``num_batch`` loop
    streams the model once per batch, ``/root/reference/main.py:22-23``).
    """

    def __init__(
        self,
        model_path: str,
        layer_names: Sequence[str],
        shards: Sequence[tuple[int, ...]],
        np_dtype,
        devices: Sequence,
        prefetch_depth: int = 1,
        tied_embeddings: bool = False,
        rounds: int = 1,
        layer_sliding=None,
        layer_rope=None,
        layer_linear=None,
        layer_index: bool = False,
        retry_policy: RetryPolicy | None = None,
        injector: FaultInjector | None = None,
        retry_recorder=None,
        integrity_recorder=None,
        verify_weights: bool = True,
        host_cache=None,
        readahead_threads: int = 2,
        residency=None,
    ):
        self.shards = list(shards)
        self.devices = list(devices)
        self.rounds = rounds
        self._stop = threading.Event()
        self._loader = _HostShardLoader(
            model_path, layer_names, np_dtype, tied_embeddings, layer_sliding,
            layer_rope, layer_linear, layer_index, retry_policy=retry_policy,
            injector=injector,
            retry_recorder=retry_recorder, retry_abort=self._stop.is_set,
            integrity=integrity_recorder, verify_weights=verify_weights,
            host_cache=host_cache, readahead_threads=readahead_threads,
        )
        # Partial residency over a broadcast: each DP chip holds its own
        # pinned copies (pinned once per chip, process lifetime); the ONE
        # host build per shard then skips the pinned layers' disk work and
        # every chip's upload skips their link bytes.
        # A planned layer not resident yet is seated from the first
        # round's own stream: the shard's ONE host build, placed on every
        # DP chip, each of which keeps its copy.
        self._residency = residency
        self._pinned_idxs: frozenset = (
            residency.frozen_pinned(self.shards)
            if residency is not None
            else frozenset()
        )
        depth = max(1, prefetch_depth)
        self._queues = [Queue(maxsize=depth) for _ in self.devices]
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    @property
    def load_time(self) -> float:
        return self._loader.load_time

    def _put(self, rank: int, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queues[rank].put(item, timeout=0.2)
                return True
            except Full:
                continue
        return False

    def _producer(self):
        for _ in range(self.rounds):
            for i, idxs in enumerate(self.shards):
                if self._stop.is_set():
                    return
                try:
                    if i + 1 < len(self.shards):
                        self._loader.warm(
                            _to_read(
                                self.shards[i + 1], self._pinned_idxs,
                                self._residency, self.devices,
                            )
                        )
                    parts = _split_parts(
                        self._loader, tuple(idxs), self._pinned_idxs,
                        self._residency, self.devices,
                    )
                    # Saved bytes counted once per HOST build — the same
                    # convention as streamed_bytes (one host build serves
                    # every DP chip).
                    for kind, idx, _ in parts:
                        if kind == "pin":
                            self._residency.note_skip(idx)
                except Exception as e:  # flscheck: disable=EXC-TAXONOMY: every producer error must reach ALL ranks as a _ShardFault envelope — a narrowed miss would hang every consumer
                    # Broadcast streams are offline (one DP run): every rank
                    # sees the failure and the run fails, so no per-shard
                    # survival here — but the envelope keeps the typed
                    # re-raise contract uniform with ShardWeightSource.
                    for rank in range(len(self.devices)):
                        self._put(rank, _ShardFault(e))
                    return
                for rank, dev in enumerate(self.devices):
                    # device_put is async — the transfers to the N chips
                    # overlap each other and the chips' compute.
                    try:
                        item = _assemble_parts(
                            parts, dev, self._loader.np_dtype,
                            self._residency,
                        )
                    except Exception as e:  # flscheck: disable=EXC-TAXONOMY: per-rank placement errors also travel as envelopes to every rank (same hang hazard as above)
                        for r2 in range(len(self.devices)):
                            self._put(r2, _ShardFault(e))
                        return
                    if not self._put(rank, item):
                        return

    def view(self, rank: int) -> "_BroadcastView":
        """The per-chip consumer handle an executor iterates one round of."""
        return _BroadcastView(self, rank)

    def close(self) -> None:
        self._stop.set()
        while self._thread.is_alive():
            for q in self._queues:
                try:
                    q.get_nowait()
                except Empty:
                    pass
            self._thread.join(timeout=0.1)
        for q in self._queues:
            while not q.empty():
                try:
                    q.get_nowait()
                except Empty:
                    break
        self._loader.close()


class SourceClosed(RuntimeError):
    """The shared weight source was closed mid-stream — a *secondary* error:
    some other DP worker failed first and orchestration closed the source to
    unblock everyone. Orchestration surfaces the root cause instead."""


class _BroadcastView:
    """One executor-side round of a BroadcastShardSource for one chip."""

    def __init__(self, parent: BroadcastShardSource, rank: int):
        self._parent = parent
        self._rank = rank

    @property
    def load_time(self) -> float:
        """The SHARED loader's cumulative host load time: the disk is read
        once for all chips, so per-chip attribution is meaningless — every
        DP executor reports the same shared total (flagged via
        ``load_time_shared``)."""
        return self._parent.load_time

    load_time_shared = True

    @property
    def bytes_loaded(self) -> int:
        """Shared loader total (one disk read serves every DP chip)."""
        return self._parent._loader.bytes_loaded

    @property
    def host_casts(self) -> int:
        """Shared loader total of host-side cast fallbacks."""
        return self._parent._loader.host_casts

    def dispatched(self) -> None:
        """The consumer's "last block enqueued" (``ShardWeightSource.
        dispatched``): a shared source's bound is its queues' alone."""

    def end_wait(self) -> str:
        """``ShardWeightSource.end_wait``: a shared source keeps every
        shard-end wait (it cannot tell what its producer uploads next)."""
        return "wait"

    def __iter__(self):
        q = self._parent._queues[self._rank]
        for idxs in self._parent.shards:
            while True:  # get with stop-check so close() can unblock us
                try:
                    item = q.get(timeout=0.2)
                    break
                except Empty:
                    if self._parent._stop.is_set():
                        raise SourceClosed(
                            "BroadcastShardSource closed while streaming "
                            "(another DP worker failed?)"
                        ) from None
            if isinstance(item, _ShardFault):
                _reraise_from_producer(item.error)
            yield idxs, item

    def close(self) -> None:
        """The shared producer outlives one view; orchestration closes it."""


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

class StreamingExecutor:
    """Single-device layer-streaming scorer — ``ShardedLlama`` equivalent.

    ``__call__(prompts)`` takes ``[(prefix_str, (suffix_str, ...)), ...]`` and
    returns one float32 ``[n_suffixes, 1, vocab]`` next-token distribution per
    prompt, exactly the reference's output contract
    (``/root/reference/utils.py:288-290``).
    """

    def __init__(
        self,
        cfg: FrameworkConfig,
        device=None,
        plan: ShardPlan | None = None,
        tokenizer=None,
        weight_source_factory: Callable[[], Any] | None = None,
    ):
        # weight_source_factory: each __call__ obtains its shard stream from
        # here instead of opening its own ShardWeightSource — DP mode passes
        # views of one shared BroadcastShardSource so the disk is read once
        # for all chips.
        self.weight_source_factory = weight_source_factory
        # Sweep-timeline tracing (obs/trace.py): enabled process-wide when
        # the config asks (--trace); a no-op bool check everywhere else.
        obs_trace.ensure_configured(cfg)
        # Flight recorder (obs/events.py + obs/incident.py): journal AND
        # incident recorder, so a programmatic batch run (no CLI) with
        # incidents_dir set still bundles its quarantines/pressure
        # events — not just journals them. One bool check per failure
        # event when unconfigured. Lazy import: incident is cold-path.
        from flexible_llm_sharding_tpu.obs import incident as obs_incident

        obs_incident.ensure_configured(cfg)
        # The executor's latest per-call stats are a registry source (the
        # batch CLI's --metrics_out and any endpoint see the same dict the
        # stats line prints). Last executor wins the name — the process-
        # wide cache/tier precedent — and the weakref source lets a
        # dropped executor be collected instead of living in the registry.
        from flexible_llm_sharding_tpu.obs.registry import weak_source

        _OBS_REGISTRY.register("executor", weak_source(self))
        self.recorder: metrics.Recorder | None = (
            metrics.Recorder(verbose=True) if cfg.verbose_metrics else None
        )
        # Transient-I/O hardening for the weight stream: retries under the
        # config's policy, per-run retry accounting, and the (off-by-
        # default) chaos injector — None when disabled, so the hot path
        # pays one is-None check.
        self._retry_policy = cfg.retry_policy()
        self._retry_recorder = metrics.RetryRecorder()
        self._injector = FaultInjector.from_config(cfg.faults)
        # Integrity accounting (detected corruption / re-read heals / block
        # recomputes / quarantines) — surfaced in stats when nonzero. The
        # manifest digest pins the model-dir CONTENT into the resume
        # signature and progress marker, so a resumed run can never consume
        # spills produced against different weights.
        self._integrity = metrics.IntegrityRecorder()
        self._manifest_digest = integrity_manifest.manifest_digest(
            integrity_manifest.load_manifest(cfg.model_path)
            if cfg.verify_weights
            else None
        )
        # Host-resident shard cache (runtime/hostcache.py): warm sweeps
        # skip disk read + parse + checksum and go straight to device_put.
        # None when disabled (host_cache_gb=0, chaos mode, unknown RAM).
        from flexible_llm_sharding_tpu.runtime import hostcache

        self._host_cache = hostcache.cache_for(cfg)
        self.cfg = cfg
        self.model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
        self.device = device
        self.dtype = _DTYPES[cfg.dtype]
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        self.tokenizer = PromptTokenizer(
            tokenizer,
            max_token_len=cfg.max_token_len,
            bucket_multiple=cfg.bucket_multiple,
        )
        # Full execution list, reference order (/root/reference/utils.py:106-107):
        # lm_head is always present; when embeddings are tied its kernel is
        # re-materialised from the embedding file.
        self.layer_names = checkpoint.layer_names_for(
            self.model_cfg.num_hidden_layers, tie_word_embeddings=False
        )
        self.plan = plan or plan_shards_dp(
            len(self.layer_names), cfg.layer_num_per_shard,
            loop_steps=self.model_cfg.total_ut_steps,
        )
        # This executor streams every layer itself, in the order of visits
        # (a looped model's stack once a step); a plan that skips or
        # reorders layers (an MP stage plan) needs the pipeline
        # runner's cross-device activation handoff, which this class does not
        # do. Order matters: activations for shard k+1 only exist after
        # shard k ran, so `covered` is compared UNSORTED, and empty shards
        # (MP round-up padding) are rejected too.
        covered = [i for s in self.plan.shards for i in s]
        if covered != visit_order(
            len(self.layer_names), self.model_cfg.total_ut_steps
        ) or not all(self.plan.shards):
            raise ValueError(
                "StreamingExecutor requires a plan covering all layers in "
                "order with no empty shards (DP/single-device); use the MP "
                "pipeline runner for interleaved stage plans"
            )
        # Device residency tier (runtime/residency.py): layers pinned in
        # HBM are subtracted from every sweep's stream — None when the
        # budget resolves to 0 (hbm_pin_gb=0, chaos auto-off, unknown HBM).
        from flexible_llm_sharding_tpu.runtime import residency

        self._residency = residency.tier_for(
            cfg, self.layer_names, self.model_cfg.tie_word_embeddings, device
        )
        self.stats: dict[str, float] = {}
        # One stats dict per executor call, in call order — callers that run
        # several batches (or DP ranks) aggregate from here rather than from
        # the last-call-wins ``self.stats``.
        self.stats_history: list[dict[str, float]] = []
        # Pallas kernels can't be auto-partitioned by GSPMD (pallas_call has
        # no sharding rule), so under TpPlacement the flash calls run inside
        # a shard_map over the heads axis (llama._flash_tp_*); the placement's
        # mesh rides into the jitted blocks as a static arg.
        # A looped model whose exit rule reads the steps' gates (a threshold
        # under 1): its exit state lives on the chip between shards and is
        # neither spilled nor recomputed.
        self._exit_state_needed = (
            self.model_cfg.total_ut_steps > 1
            and self.model_cfg.early_exit_threshold < 1
        )
        self._use_pallas = cfg.pallas_enabled()
        self._tp_mesh = (
            device.mesh if hasattr(device, "segment_target") else None
        )

    # -- numpy dtype for host-side casting ---------------------------------
    @property
    def _np_dtype(self):
        return np_dtype_for(self.cfg.dtype)

    def _tokenize(self, prompts) -> list[TokenizedPrompt]:
        toks = [self.tokenizer(p, s) for p, s in prompts]
        # Scoring is one full forward per pass, so only within-prompt
        # regime uniformity matters (the slow generation loop re-chooses
        # the table each pass, exactly like HF's full recompute).
        check_longrope_regime(self.model_cfg, toks)
        check_dense_len(self.model_cfg, toks)
        return toks

    # -- disk-mode crash resume (markers shared with the pipeline: see
    # runtime/resume.py for the signature/marker contract) -----------------

    def _resume_signature(self, toks) -> str:
        return resume.workload_signature(
            toks, self.plan.shards, self.cfg.model_path,
            self.cfg.dtype, self.cfg.block_size,
            manifest_digest=self._manifest_digest,
        )

    def _progress_path(self, store: ActivationStore, sig: str) -> str:
        return resume.marker_path(self.cfg.disk_folder, sig, store.tag)

    def _resume_start(self, store: ActivationStore, sig: str) -> int:
        """First shard a resumed run must execute.

        Safe against mid-shard crashes because disk stores ping-pong between
        two file generations (ActivationStore.set_shard): shard k writes
        generation k%2 and reads (k-1)%2, so a crashed shard k can never
        have destroyed its own inputs — the resumed run simply rewrites
        shard k's outputs from the intact previous generation.
        """
        if not (self.cfg.resume and self.cfg.storage_location == "disk"):
            return 0
        if self._exit_state_needed:
            # The marker counts visits, but the exit state of the steps
            # before the crash was on the chip: the rule that gives each
            # scored token its step cannot be resumed, so the pass restarts.
            return 0
        data = resume.read_marker(
            self._progress_path(store, sig), sig,
            manifest_hash=self._manifest_digest,
        )
        # The final shard produces the scores and is never marked complete,
        # so start is always < num_shards.
        return min(int(data.get("completed_shards", 0)), len(self.plan.shards) - 1)

    def _mark_progress(self, store: ActivationStore, sig: str, done: int) -> None:
        resume.write_marker(
            self._progress_path(store, sig), sig, completed_shards=done,
            manifest_hash=self._manifest_digest,
        )

    def __call__(
        self, prompts, batch: int = 0, clock: SweepClock | None = None
    ) -> list[np.ndarray]:
        # batch: the num_batch loop index (scopes disk activation files and
        # the resume marker per batch — see ActivationStore).
        # clock: the pass's account, already running when orchestration
        # opened it before building this executor (a call's first pass);
        # opened here otherwise. The pass finishes it; an error closes it.
        with clock or SweepClock() as clock:
            return self._run_pass(prompts, batch, clock)

    def _run_pass(self, prompts, batch: int, clock: SweepClock) -> list[np.ndarray]:
        clock.model = self.model_cfg
        with obs_trace.span("tokenize", cat="sweep", sweep_id=clock.sweep_id):
            toks = self._tokenize(prompts)
        blocks = make_blocks(toks, self.cfg.block_size)
        location, device_budget = self.cfg.storage_location, 0
        if location is None:
            # Nobody said where: a 'cpu' store that keeps on the chip the
            # blocks that fit what the chip has free by the tier's plan.
            from flexible_llm_sharding_tpu.runtime import residency

            location = "cpu"
            tied = self.model_cfg.tie_word_embeddings
            width = self.model_cfg.hidden_size * self.model_cfg.hc_mult
            device_budget = residency.make_room_for_activations(
                self.device,
                self._residency,
                residency.in_flight_bytes(self.cfg, self.layer_names, tied),
                # one generation of the pass's blocks: every prompt's prefix
                # bucket and its suffixes' rows, a residual's width each
                sum(
                    t.prefix_ids.size + t.suffix_ids.size for t in toks
                ) * width * self._np_dtype.itemsize,
                tied,
            )
        store = ActivationStore(
            location,
            self.cfg.disk_folder,
            device_rank=self.plan.device_rank,
            rank_tag=self.plan.num_devices > 1 and self.cfg.data_parallel,
            max_in_cpu=self.cfg.max_activation_in_cpu,
            np_dtype=self._np_dtype,
            batch=batch,
            injector=self._injector,
            integrity=self._integrity,
            # Spill WRITES retry under the same policy as the weight
            # stream's reads (disk_full/ENOSPC is transient when the
            # pressure ladder frees space); retries land in io_retries
            # under the 'spill_write' label.
            retry_policy=self._retry_policy,
            retry_recorder=self._retry_recorder,
            device_budget=device_budget,
        )
        resumable = self.cfg.storage_location == "disk"
        sig = self._resume_signature(toks) if resumable else ""
        start_shard = self._resume_start(store, sig) if resumable else 0
        # Per-call hash/cache amortization baselines (deltas reported in
        # stats), captured BEFORE the source's prefetch producer can run.
        # Cache counters are process-wide; a shared (DP broadcast) source
        # interleaves every rank's loads, so deltas are only attributed
        # when this executor owns its source.
        own_source = self.weight_source_factory is None
        cache_before = (
            self._host_cache.stats()
            if (self._host_cache is not None and own_source)
            else None
        )
        verdict_before = (
            integrity_manifest.verdict_stats() if own_source else None
        )
        residency_before = (
            self._residency.stats()
            if (self._residency is not None and own_source)
            else None
        )
        # Per-block device-resident metadata, uploaded once, BEFORE the source
        # exists: its thread starts enqueuing weight uploads at once, and
        # transfers run in order on the link.
        block_meta = {}
        for b, idxs in enumerate(blocks):
            block_meta[b] = (
                jnp.asarray(np.stack([toks[i].prefix_ids for i in idxs])),
                jnp.asarray(np.stack([toks[i].suffix_ids for i in idxs])),
                jnp.asarray(
                    np.array([toks[i].prefix_len for i in idxs], dtype=np.int32)
                ),
                jnp.asarray(np.stack([toks[i].suffix_eos for i in idxs])),
            )
        if self.weight_source_factory is not None:
            # Shared (DP broadcast) source: it streams EVERY shard to every
            # chip — a resuming rank cannot slice the stream, so it consumes
            # and discards the already-completed shards' weights instead
            # (skip below). Each rank keeps its own progress marker (the
            # store's rank tag), so ranks may resume from different shards.
            source = self.weight_source_factory()
            skip = start_shard
            # Shared source: its producer thread has been running since
            # orchestration built it, so the delta below is this call's
            # WINDOW of the shared stream (flagged streamed_bytes_shared).
            bytes_before = getattr(source, "bytes_loaded", None)
        else:
            source = ShardWeightSource(
                self.cfg.model_path,
                self.layer_names,
                self.plan.shards[start_shard:],
                self._np_dtype,
                device=self.device,
                prefetch_depth=self.cfg.effective_prefetch_depth(),
                tied_embeddings=self.model_cfg.tie_word_embeddings,
                layer_sliding=self.model_cfg.layer_sliding,
                layer_rope=self.model_cfg.layer_rope,
                layer_linear=self.model_cfg.layer_linear,
                layer_index=llama.layer_indexed(self.model_cfg),
                retry_policy=self._retry_policy,
                injector=self._injector,
                retry_recorder=self._retry_recorder,
                integrity_recorder=self._integrity,
                verify_weights=self.cfg.verify_weights,
                host_cache=self._host_cache,
                readahead_threads=self.cfg.readahead_threads,
                residency=self._residency,
                sweep_id=clock.sweep_id,
                first_shard_idx=start_shard,
                # The link may start at a seated head where the host can run
                # ahead of the device through it: one chip, blocks kept on
                # it, no progress marker to keep behind the device.
                lead=(
                    _last_head_record()
                    if self._tp_mesh is None and not resumable
                    and (location == "tpu" or device_budget > 0)
                    else None
                ),
            )
            skip = 0
            # Baseline taken BEFORE the source's prefetch producer starts
            # (it launches in the constructor and can finish shard 0 before
            # any post-construction read) — a fresh loader starts at 0.
            bytes_before = 0

        scores: dict[int, np.ndarray] = ScoreSink(
            max_device=self.cfg.score_sink_max_device
        )

        def on_shard_done(local_idx: int) -> None:
            if resumable:
                # Own source yields from start_shard; a shared source yields
                # from 0 with the skipped prefix re-marked harmlessly.
                done = local_idx + 1 + (0 if skip else start_shard)
                if done < len(self.plan.shards):  # final shard re-runs always
                    # The marker must never claim a shard whose activation
                    # writes are still queued in the async disk writer.
                    store.flush()
                    self._mark_progress(store, sig, done)

        try:
            self._stream(
                source,
                store,
                toks,
                blocks,
                block_meta,
                scores,
                on_shard_done,
                n_shards=len(self.plan.shards) - start_shard,
                skip=skip,
                start_shard=start_shard,
                clock=clock,
            )
        except BaseException:
            # Error path: retire the async disk writer and drop stored
            # buffers — a leaked writer pins device arrays in HBM for the
            # process lifetime. (Success path clears after stats, below,
            # which also acts as the final write barrier.)
            try:
                store.clear()
            except Exception:  # flscheck: disable=EXC-TAXONOMY: best-effort cleanup on the error path; the _stream exception re-raised below is the root cause and must not be masked
                pass  # the _stream exception is the root cause; keep it
            raise
        finally:
            source.close()
        finalize_scores(scores)
        if resumable:  # completed: drop the marker
            resume.remove_marker(self._progress_path(store, sig))

        self.stats = {
            "load_weights_time_s": source.load_time,
            # From the pass's clock, both host time around asynchronous
            # dispatch: the compute spans' total (dispatch plus the waits
            # for the device inside them, not device busy time), and the
            # driver time blocked waiting on the weight source — the
            # produce time prefetch did NOT hide (serialized schedule ->
            # ~all of produce_wall_s; perfect overlap -> the first shard
            # only).
            "compute_wall_s": clock.compute_s,
            "source_wait_s": clock.source_wait_s,
            # The producer's whole per-shard wall (host load + device
            # placement dispatch) — overlap_efficiency's denominator.
            # Absent on shared (broadcast) sources, whose producer serves
            # every rank at once.
            **(
                {"produce_wall_s": source.produce_time}
                if getattr(source, "produce_time", None) is not None
                else {}
            ),
            "total_wall_s": time.perf_counter() - clock.t0,
            "num_layers_streamed": float(self.plan.num_local_layers),
            "tokens_processed": float(sum(t.tokens_processed for t in toks)),
        }
        if getattr(source, "load_time_shared", False):
            # DP broadcast: the disk is read once for all chips; this stat is
            # the shared total, not this chip's own.
            self.stats["load_time_shared"] = 1.0
        if bytes_before is not None:
            # Delta over this call's window. On a shared (broadcast) source
            # the loader serves every rank at once, so the delta is the
            # SHARED bytes loaded during this rank's window, not this
            # chip's own traffic — flagged like load_time_shared.
            self.stats["streamed_bytes"] = float(
                source.bytes_loaded - bytes_before
            )
            if getattr(source, "load_time_shared", False):
                self.stats["streamed_bytes_shared"] = 1.0
        from flexible_llm_sharding_tpu.runtime.residency import probe_chip

        # self.device may be a placement target (TpPlacement): probe a chip.
        peak = metrics.peak_hbm_gb(probe_chip(self.device))
        if self._residency is not None:
            # HBM accounting honesty: the pin tier is device-resident for
            # the whole run, so the reported peak can never sit below it —
            # including on the CPU backend, whose allocator reports no
            # stats, where the tier's own bytes become the floor figure.
            pinned_gb = self._residency.pinned_device_bytes(self.device) / 1e9
            if pinned_gb:
                peak = max(peak or 0.0, pinned_gb)
        if peak is not None:
            self.stats["peak_hbm_gb"] = peak
        io_retries = self._retry_recorder.total("retries")
        if io_retries:
            # Transient I/O faults absorbed by the retry layer this run —
            # non-zero means the stream RECOVERED from real (or injected)
            # blips; absent means the run was clean.
            self.stats["io_retries"] = float(io_retries)
        for k, v in self._integrity.snapshot().items():
            # Corruption accounting (integrity_failures / reread_heals /
            # recomputes / quarantined_shards): nonzero means checksums
            # CAUGHT bad bytes and the run healed around them; absent
            # means every byte verified clean.
            if v:
                self.stats[k] = float(v)
        if cache_before is not None:
            # Host shard cache amortization over THIS call's window: a warm
            # steady-state sweep is all hits (disk read/parse/verify
            # skipped; the device_put still runs per sweep).
            after = self._host_cache.stats()
            hits = after["hits"] - cache_before["hits"]
            misses = after["misses"] - cache_before["misses"]
            self.stats["host_cache_hits"] = float(hits)
            self.stats["host_cache_misses"] = float(misses)
            if hits + misses:
                self.stats["host_cache_hit_rate"] = round(
                    hits / (hits + misses), 4
                )
        if verdict_before is not None:
            # crc amortization: full hash passes actually run vs loads that
            # reused a cached clean verdict (hash once per file generation,
            # not once per sweep).
            v_after = integrity_manifest.verdict_stats()
            for key in ("verdict_hits", "full_verifies"):
                delta = v_after[key] - verdict_before[key]
                if delta:
                    self.stats[f"crc_{key}"] = float(delta)
        if residency_before is not None:
            # Partial-residency accounting over THIS call's window: every
            # sweep's streamed_bytes drops by exactly the pinned layers'
            # host bytes; the saved traffic is reported alongside so the
            # drop can be audited (pinned_bytes is the resident HBM cost
            # on this executor's placement target).
            r_after = self._residency.stats()
            self.stats["pinned_bytes"] = float(
                self._residency.pinned_device_bytes(self.device)
            )
            saved = (
                r_after["stream_bytes_saved"]
                - residency_before["stream_bytes_saved"]
            )
            hits = r_after["pin_hits"] - residency_before["pin_hits"]
            if saved:
                self.stats["stream_bytes_saved"] = float(saved)
            if hits:
                self.stats["pin_hits"] = float(hits)
        host_casts = getattr(source, "host_casts", None)
        if host_casts:
            # Host-side dtype casts the stream could NOT defer to the chip
            # (fallback dtypes only) — nonzero flags a CPU-bound cast on
            # the hot path.
            self.stats["host_casts"] = float(host_casts)
        self.stats_history.append(dict(self.stats))
        if self.recorder is not None:
            self.recorder.record(
                "executor_call",
                self.stats["total_wall_s"],
                prompts=len(prompts),
                **{k: v for k, v in self.stats.items() if k != "total_wall_s"},
            )
        store.clear()
        clock.finish(source, store)
        return [scores[i] for i in range(len(prompts))]

    def _stream(
        self,
        source,
        store,
        toks,
        blocks,
        block_meta,
        scores,
        on_shard_done=None,
        n_shards: int | None = None,
        skip: int = 0,
        start_shard: int = 0,
        *,
        clock: SweepClock,
    ) -> None:
        n_layers = len(self.layer_names)
        visits = self.plan.visits()
        # Per block (Lp, S, Ls, the prompts' true prefix lengths): what the
        # flash kernels' step count is made from (``_flash_steps``).
        block_shapes = [
            (
                *toks[idxs[0]].prefix_ids.shape,
                *toks[idxs[0]].suffix_ids.shape,
                tuple(toks[i].prefix_len for i in idxs),
            )
            for idxs in blocks
        ]
        # Rows a block puts through a layer (prompts x (Lp + S x Ls)); the
        # sweep's span carries them for the trace report.
        clock.set_block_rows(
            len(lens) * (lp + s * ls) for lp, s, ls, lens in block_shapes
        )
        total = (n_shards or len(self.plan.shards)) * max(len(blocks), 1)
        bar = metrics.progress_bar(total, desc="stream", unit="blk")
        it = enumerate(source)
        # Spill-corruption self-healing (disk mode only — cpu/tpu stores pop
        # their in-memory activations on fetch, so there is nothing left to
        # recompute from): the PREVIOUS shard's weights are retained one
        # extra iteration so a block whose spill fails verification can be
        # re-derived from the last good shard boundary — disk's generation
        # ping-pong guarantees the previous shard's own inputs are still
        # intact. Costs one extra shard's worth of HBM while streaming in
        # disk mode (comparable to prefetch_depth=1's queued shard).
        heal_spills = store.location == "disk" and not self._exit_state_needed
        prev_shard = None  # (visit, segments) of the last shard run
        # (last result, stamps) of a shard whose end-wait lags into the next
        lagging = None
        # A looped model: one span around each step's shards.
        step_span = None
        # Correlation id for this full pass over the shards — the offline
        # equivalent of one serving sweep; every span below carries it so
        # the trace analyzer can group a pass's phases back together.
        sweep_id = clock.sweep_id
        try:
            while True:
                # The head ends at the consumer's first wait for a shard:
                # that wait, which nothing can overlap, is the sweep's
                # first source_wait.
                clock.end_head()
                with obs_trace.timed(
                    "source_wait", cat="sweep", sweep_id=sweep_id
                ) as wait:
                    try:
                        shard_i, (layer_idxs, segments) = next(it)
                    except StopIteration:
                        wait.drop()
                        break
                    if shard_i < skip:
                        # Resume over a shared source: this shard already
                        # ran in the crashed attempt; drop its broadcast
                        # weights unused. Its wait is NOT counted against
                        # overlap efficiency — skipped shards run no
                        # compute that could hide it — so the trace's
                        # source_wait total matches the stats line's
                        # overlap-efficiency definition exactly.
                        wait.drop()
                        del segments
                        continue
                # Global shard index: shared sources yield every shard
                # from 0 (skip consumed the resumed prefix); an own
                # source yields only the resumed tail.
                shard_idx = shard_i + (0 if skip else start_shard)
                # Driver time blocked on the weight source — the exact
                # NOT-hidden load time (prefetch hides the rest); the
                # numerator of overlap_efficiency (cli stats line).
                clock.take(shard_idx, wait)
                visit = visits[shard_idx]
                clock.layer_visits += decoder_visits(layer_idxs, n_layers)
                clock.flash_steps += _flash_steps(
                    self.model_cfg, layer_idxs, n_layers, block_shapes,
                    self._use_pallas, self._tp_mesh,
                )
                # The embedding rides the first step's span, the head (whose
                # shard starts after the last norm) the last step's.
                step = min(visit.step, self.plan.loop_steps - 1)
                if self.plan.loop_steps > 1 and (
                    step_span is None or step_span[0] != step
                ):
                    if step_span is not None:
                        step_span[1].__exit__(None, None, None)
                    step_span = (step, obs_trace.span(
                        "loop_step", cat="sweep", sweep_id=sweep_id, step=step,
                    ))
                    step_span[1].__enter__()
                store.set_shard(shard_idx)
                store.trace_ids = clock.span_ids()
                with obs_trace.timed(
                    "compute", cat="sweep", sweep_id=sweep_id,
                    shard_idx=shard_idx,
                ) as compute:
                    lagging = self._stream_shard(
                        store, toks, blocks, block_meta, scores,
                        visit, segments, prev_shard, bar, clock, compute, source,
                        lagging,
                    )
                    if on_shard_done is not None:
                        on_shard_done(shard_i)
                clock.shard_done(compute)
                prev_shard = (visit, segments) if heal_spills else None
            clock.start_tail()
        finally:
            if step_span is not None:
                step_span[1].__exit__(None, None, None)
            bar.close()

    def _stream_shard(
        self, store, toks, blocks, block_meta, scores, visit, segments,
        prev_shard, bar, clock, compute, source, lagging,
    ):
        """One shard's compute over every block — the body the traced
        ``compute`` span (``compute``) wraps in ``_stream``: its ``dispatch`` child is
        the consumer's pass over the blocks (inside it, the activation
        store's own ``device_wait`` where it resolves a block's copy), its
        ``device_wait`` child the wait for the device at the shard's end
        (the spill-corruption recompute path lives here).

        ``lagging``: ``(last result, stamps)`` of the shard before, whose
        end-wait lagged into this shard, or None. It is waited for inside
        the dispatch, before the last block: that block's step takes the
        result's buffers (a decoder step donates its activations). Returns
        this shard's own pair where its wait lags in turn, else None."""
        sweep_id = clock.sweep_id
        ids = clock.span_ids()
        link_bytes = store.link_bytes
        with obs_trace.span("dispatch", cat="sweep", **ids):
            for b, idxs in enumerate(blocks):
                if lagging is not None and b == len(blocks) - 1:
                    clock.wait_for_shard(*lagging)
                    lagging = None
                fetched = None
                while True:
                    try:
                        suffix_h = process_block(
                            self.model_cfg,
                            self.dtype,
                            segments,
                            visit,
                            store,
                            b,
                            idxs,
                            block_meta[b],
                            self.device,
                            toks,
                            scores,
                            use_pallas=self._use_pallas,
                            tp_mesh=self._tp_mesh,
                            fetched=fetched,
                            clock=clock,
                        )
                        break
                    except SpillCorruptError:
                        # The block's input spill is corrupt even after
                        # re-reads. Recompute it from the last good shard
                        # boundary — bounded to ONE recompute per block
                        # per shard (a recompute that fails again means
                        # the previous generation is corrupt too: raise).
                        if prev_shard is None or fetched is not None:
                            raise
                        self._integrity.count("recomputes")
                        obs_trace.instant(
                            "spill_recompute", cat="integrity", block=b,
                            sweep_id=sweep_id,
                        )
                        obs_events.emit(
                            "spill_recompute", block=b, sweep_id=sweep_id
                        )
                        fetched = self._recompute_block(
                            prev_shard, store, b, idxs, block_meta[b], toks
                        )
                if b == 0:
                    # The shard's first steps are enqueued: seconds from the
                    # compute span's start, for the timeline and the trace.
                    compute.attrs["launch_s"] = clock.launched() - compute.t0
                bar.update(1)
            if not blocks:
                bar.update(1)
        # The shard's last block is enqueued: the producer's next upload may
        # go out now, behind these steps and beside them.
        source.dispatched()
        # The wait for the shard's results at its end (blocks can be empty:
        # num_batch > prompt count -> ex([])) is the host's brake: it holds
        # a streamed shard's buffers to one shard in flight, it puts the
        # next shard's launches onto an empty device queue (an upload the
        # signal above lets out runs beside these steps, and the next shard
        # is taken only once it arrived, so nothing is launched under an
        # upload), and a disk pass's progress marker (on_shard_done) never
        # runs ahead of the device. Where none of that is needed it lags
        # one shard, so the device has this shard queued while the host
        # takes and dispatches the next: the blocks stayed on the chip (the
        # store sent none over the link, which a disk pass, resumed from its
        # marker and healing spills from the shard before, always does) and
        # the source says no upload can go out behind the launches queued
        # meanwhile. A shard of a seated head before its last is not waited
        # for at all: the host runs ahead, so the device still has the head
        # queued when the uploads held behind it go out (end_wait).
        if not (blocks and visit.stores):
            return None
        stamps = clock.shards[-1]
        end = source.end_wait() if store.link_bytes == link_bytes else "wait"
        if end == "wait":
            clock.wait_for_shard(suffix_h, stamps)
            return None
        clock.waits_deferred += 1
        return (suffix_h, stamps) if end == "lag" else None

    def _recompute_block(self, prev_shard, store, b, idxs, meta, toks):
        """Re-derive one block's activations by re-running the PREVIOUS
        shard: its inputs live in the other disk generation (the ping-pong
        that protects crash resume also protects this path — shard k-1's
        inputs at generation k%2 are untouched until shard k stores this
        very block). Returns (prefix_h, suffix_h) on device, ready to feed
        the current shard via ``process_block(fetched=...)``."""
        prev_visit, prev_segments = prev_shard
        prefix_ids, suffix_ids, prefix_len, suffix_eos = meta
        if prev_visit.embeds:
            prefix_h, suffix_h = None, None  # re-embed from token ids
        else:
            prefix_h, suffix_h = store.fetch_recompute(
                b, idxs, with_prefix=prev_visit.needs_prefix
            )
            act_target = getattr(self.device, "act", self.device)
            suffix_h = jax.device_put(suffix_h, act_target)
            if prefix_h is not None:
                prefix_h = jax.device_put(prefix_h, act_target)
        prefix_h, suffix_h, _ = apply_segments(
            self.model_cfg,
            self.dtype,
            prev_segments,
            prefix_h,
            suffix_h,
            prefix_ids,
            suffix_ids,
            prefix_len,
            suffix_eos,
            self._use_pallas,
            self._tp_mesh,
            # A looped model's step ends are re-run for their norms alone:
            # the block's exit state already holds what the first run gave
            # it (a pass whose exit rule reads that state does not heal).
            loop=LoopPlace.of(self.model_cfg, prev_visit, {}, b, idxs, toks),
        )
        return prefix_h, suffix_h


__all__ = [
    "StreamingExecutor",
    "ShardWeightSource",
    "BroadcastShardSource",
    "process_host_casts",
    "process_slow_sweeps",
    "process_sweep_log",
    "process_tied_head_requants",
    "SweepClock",
    "ShardLoadError",
    "ShardCorruptError",
    "SpillCorruptError",
    "apply_segments",
    "process_block",
    "finalize_scores",
    "ScoreSink",
    "SourceClosed",
]
