"""MP mode: the interleaved layer pipeline across the chips of a slice.

Reference semantics (``/root/reference/utils.py:151-157,189-213`` and the
``multigpu_flexibility.png`` diagram): contiguous layer shards are assigned
round-robin to devices (shard k -> device k % N), and a prompt's activations
hop device-to-device between stages. The reference coordinates this with
Python threads, a shared activation dict, a ``prompt2layer`` progress table
polled at 1-second granularity, and (in disk mode) ``.npy`` files as the
wrap-around transport from the last rank back to rank 0.

TPU-native redesign (SURVEY.md §2.3, §7):

- One host thread drives ALL stages in global execution order; there is no
  polling control plane. Pipeline concurrency is *emergent from XLA's async
  dispatch*: the host enqueues stage s+1's jitted call on chip B as soon as
  stage s's output on chip A is dispatched (not completed); the runtime
  orders them by data dependency, so chip A computes block b+1 while chip B
  computes block b — the reference's per-prompt pipelining without a single
  lock or sleep.
- Activation hops are ``jax.device_put`` of device-resident arrays —
  chip-to-chip DMA over ICI (``storage_location='tpu'``), never staged
  through host RAM the way the reference's ``.cpu()``/``.to(device)`` pairs
  are. ``cpu``/``disk`` modes keep the reference's host/disk transports
  (including the per-prompt ``.npy`` file contract for resumability).
- Weights for stage t+1 upload to *that stage's chip* while stage t computes
  (per-shard target devices in ShardWeightSource), so weight streaming and
  compute overlap across the whole pipeline.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.obs import trace as _trace
from flexible_llm_sharding_tpu.parallel.planner import (
    batch_ranges,
    global_stage_order,
    shard_visit,
)
from flexible_llm_sharding_tpu.runtime import resume
from flexible_llm_sharding_tpu.runtime.activations import ActivationStore
from flexible_llm_sharding_tpu.runtime.executor import (
    ScoreSink,
    ShardWeightSource,
    _DTYPES,
    finalize_scores,
    np_dtype_for,
    process_block,
)
from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer, make_blocks
from flexible_llm_sharding_tpu.utils import checkpoint, metrics


class PipelineRunner:
    """Drives one full scoring pass through the interleaved stage pipeline."""

    def __init__(self, cfg: FrameworkConfig, devices, tokenizer=None):
        from flexible_llm_sharding_tpu.obs.registry import (
            REGISTRY,
            weak_source,
        )

        _trace.ensure_configured(cfg)
        REGISTRY.register("pipeline", weak_source(self))
        self.cfg = cfg
        self.devices = list(devices)
        self.model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
        self.model_cfg.require_one_attention_shape("the pipeline runner")
        self.model_cfg.require_single_visit("the pipeline runner")
        self.dtype = _DTYPES[cfg.dtype]
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        self.tokenizer = PromptTokenizer(
            tokenizer,
            max_token_len=cfg.max_token_len,
            bucket_multiple=cfg.bucket_multiple,
        )
        self.layer_names = checkpoint.layer_names_for(
            self.model_cfg.num_hidden_layers, tie_word_embeddings=False
        )
        # (stage_idx, device_rank, layer_tuple) in execution order.
        self.stages = global_stage_order(
            len(self.layer_names), cfg.layer_num_per_shard, len(self.devices)
        )
        self.stats: dict[str, float] = {}
        self._use_pallas = cfg.pallas_enabled()
        # Per-stage dispatch events; ``dispatch_wall_s`` vs ``total_wall_s``
        # in stats is the pipelining evidence — see _run_batch.
        self.recorder = metrics.Recorder(verbose=cfg.verbose_metrics)
        # Model-content pin for resume (mirrors StreamingExecutor): the
        # manifest digest rides in the workload signature and the progress
        # marker, so a resumed pipeline never consumes inter-stage spills
        # produced against different weights.
        from flexible_llm_sharding_tpu.integrity import manifest as _iman

        self._manifest_digest = _iman.manifest_digest(
            _iman.load_manifest(cfg.model_path) if cfg.verify_weights else None
        )

    @property
    def _np_dtype(self):
        return np_dtype_for(self.cfg.dtype)

    def __call__(self, prompts) -> list[np.ndarray]:
        out: list[np.ndarray] = []
        for i, (lo, hi) in enumerate(batch_ranges(len(prompts), self.cfg.num_batch)):
            out += self._run_batch(prompts[lo:hi], batch=i)
        return out

    # -- disk-mode crash resume (MP counterpart of the executor's) ---------
    # In disk mode every inter-stage handoff is a durable per-prompt .npy
    # pair (generation ping-pong: see ActivationStore.set_shard), so a
    # crashed pipeline restarts from the last fully-stored stage — even a
    # mid-stage crash, whose partial writes went to the OTHER generation.
    # The signature (runtime/resume.py) guards against resuming into a
    # different checkpoint, workload, stage plan, or device count (rank
    # assignment is part of the stage tuples).

    def _resume_signature(self, toks) -> str:
        return resume.workload_signature(
            toks,
            ("mp", [(r, s) for (_, r, s) in self.stages]),
            self.cfg.model_path,
            self.cfg.dtype,
            self.cfg.block_size,
            manifest_digest=self._manifest_digest,
        )

    def _marker_path(self, sig: str, tag: str) -> str:
        return resume.marker_path(self.cfg.disk_folder, sig, tag)

    def _resume_start(self, sig: str, tag: str, last_real: int) -> int:
        if not (self.cfg.resume and self.cfg.storage_location == "disk"):
            return 0
        data = resume.read_marker(
            self._marker_path(sig, tag), sig,
            manifest_hash=self._manifest_digest,
        )
        # The head stage produces the scores and is never marked complete.
        return min(int(data.get("completed_stages", 0)), last_real)

    def _mark_stage(self, sig: str, tag: str, done: int) -> None:
        resume.write_marker(
            self._marker_path(sig, tag), sig, completed_stages=done,
            manifest_hash=self._manifest_digest,
        )

    def _run_batch(self, prompts, batch: int = 0) -> list[np.ndarray]:
        t_start = time.perf_counter()
        toks = [self.tokenizer(p, s) for p, s in prompts]
        blocks = make_blocks(toks, self.cfg.block_size)
        store = ActivationStore(
            # Not set reads as 'cpu' here: 'tpu' is the user's order for
            # the chip-to-chip hop, never derived.
            self.cfg.storage_location or "cpu",
            self.cfg.disk_folder,
            max_in_cpu=self.cfg.max_activation_in_cpu,
            np_dtype=self._np_dtype,
            batch=batch,
            # Spill writes retry ENOSPC under the run's policy (typed
            # DiskFullError on exhaustion) — same contract as the
            # single-device executor's store.
            retry_policy=self.cfg.retry_policy(),
        )
        resumable = self.cfg.storage_location == "disk"
        last_real = max(
            (i for i, (_, _, s) in enumerate(self.stages) if s), default=0
        )
        sig = self._resume_signature(toks) if resumable else ""
        start_stage = (
            self._resume_start(sig, store.tag, last_real) if resumable else 0
        )
        stage_shards = [s for (_, _, s) in self.stages[start_stage:]]
        stage_devs = [self.devices[r] for (_, r, _) in self.stages[start_stage:]]
        from flexible_llm_sharding_tpu.faults.inject import FaultInjector
        from flexible_llm_sharding_tpu.runtime import hostcache, residency

        # Partial residency over the pipeline: a pinned layer stays on its
        # STAGE's chip (the source seats each planned layer from its own
        # stream, on the device of the layer's shard), so each stage's
        # sweep skips its own pins.
        tier = residency.tier_for(
            self.cfg,
            self.layer_names,
            self.model_cfg.tie_word_embeddings,
            self.devices[0],
        )
        source = ShardWeightSource(
            self.cfg.model_path,
            self.layer_names,
            stage_shards,
            self._np_dtype,
            devices=stage_devs,
            prefetch_depth=self.cfg.effective_prefetch_depth(),
            tied_embeddings=self.model_cfg.tie_word_embeddings,
            layer_sliding=self.model_cfg.layer_sliding,
            layer_rope=self.model_cfg.layer_rope,
            retry_policy=self.cfg.retry_policy(),
            injector=FaultInjector.from_config(self.cfg.faults),
            verify_weights=self.cfg.verify_weights,
            host_cache=hostcache.cache_for(self.cfg),
            readahead_threads=self.cfg.readahead_threads,
            residency=tier,
        )

        n_layers = len(self.layer_names)
        scores: dict[int, np.ndarray] = ScoreSink(
            max_device=self.cfg.score_sink_max_device
        )
        # Block metadata is uploaded per device on first use (jit operands
        # must be colocated with that stage's weights).
        host_meta = {
            b: (
                np.stack([toks[i].prefix_ids for i in idxs]),
                np.stack([toks[i].suffix_ids for i in idxs]),
                np.array([toks[i].prefix_len for i in idxs], dtype=np.int32),
                np.stack([toks[i].suffix_eos for i in idxs]),
            )
            for b, idxs in enumerate(blocks)
        }
        dev_meta: dict[tuple[int, int], tuple] = {}

        def meta_on(b: int, dev) -> tuple:
            key = (b, id(dev))
            if key not in dev_meta:
                dev_meta[key] = tuple(
                    jax.device_put(a, dev) for a in host_meta[b]
                )
            return dev_meta[key]

        bar = metrics.progress_bar(
            (len(self.stages) - start_stage) * max(len(blocks), 1),
            desc="pipeline",
            unit="blk",
        )
        try:
            for ((stage_idx, rank, layer_idxs), (_, segments)) in zip(
                self.stages[start_stage:], source
            ):
                if not layer_idxs:  # round-up padding stage
                    bar.update(max(len(blocks), 1))
                    continue
                store.set_shard(stage_idx)
                dev = self.devices[rank]
                t_stage = time.perf_counter()
                with _trace.span(
                    "pipeline_stage", cat="pipeline", stage=stage_idx,
                    rank=rank,
                ):
                    for b, idxs in enumerate(blocks):
                        process_block(
                            self.model_cfg,
                            self.dtype,
                            segments,
                            shard_visit(layer_idxs, n_layers),
                            store,
                            b,
                            idxs,
                            meta_on(b, dev),
                            dev,
                            toks,
                            scores,
                            use_pallas=self._use_pallas,
                        )
                        bar.update(1)
                source.dispatched()  # the next upload goes out behind this stage's steps
                self.recorder.record(
                    "stage_dispatch",
                    time.perf_counter() - t_stage,
                    stage=stage_idx,
                    rank=rank,
                )
                if resumable and stage_idx < last_real:
                    # Durable-store barrier, then advance the marker; disk
                    # mode is already file-synchronized stage-to-stage, so
                    # this flush costs nothing extra.
                    store.flush()
                    self._mark_stage(sig, store.tag, stage_idx + 1)
        except BaseException:
            # Same hazard as StreamingExecutor's error path: a leaked async
            # disk writer would pin queued device arrays in HBM.
            try:
                store.clear()
            except Exception:  # flscheck: disable=EXC-TAXONOMY: best-effort cleanup on the error path; the stream exception re-raised below is the root cause and must not be masked
                pass  # the stream exception is the root cause; keep it
            raise
        finally:
            bar.close()
            source.close()
        # All stages are now DISPATCHED; nothing above host-synced (tpu
        # storage: activation hops are device-to-device, head scores copy
        # back asynchronously). dispatch_wall << total_wall is the evidence
        # that the driver ran ahead of the chips — XLA executes each chip's
        # queue independently, so stage s+1 on chip B overlaps stage s on
        # chip A exactly as the reference's emergent per-prompt pipelining
        # does (/root/reference/utils.py:185-213), with zero polling.
        dispatch_wall = time.perf_counter() - t_start
        finalize_scores(scores)
        if resumable:  # completed: drop the marker
            resume.remove_marker(self._marker_path(sig, store.tag))

        self.stats = {
            "load_weights_time_s": source.load_time,
            "dispatch_wall_s": dispatch_wall,
            "total_wall_s": time.perf_counter() - t_start,
            "num_stages": float(len(self.stages)),
            "tokens_processed": float(sum(t.tokens_processed for t in toks)),
        }
        if tier is not None:
            rs = tier.stats()
            # Process-wide gauge (per-stage pins sum across the chips).
            self.stats["pinned_bytes"] = float(rs["pinned_bytes"])
        store.clear()
        return [scores[i] for i in range(len(prompts))]


def run_pipeline(
    cfg: FrameworkConfig, prompts, devices, tokenizer=None
) -> list[np.ndarray]:
    return PipelineRunner(cfg, devices, tokenizer=tokenizer)(list(prompts))


__all__ = ["PipelineRunner", "run_pipeline"]
