"""Long-context scoring: sequence-parallel (prefix, suffixes) prompts.

The reference hard-caps sequence length at 4096 and silently truncates
(``/root/reference/utils.py:14,250,254``). Here a prompt whose prefix
overflows one chip's bucket is scored EXACTLY by sharding the prefix over an
``sp`` mesh axis:

- Prefix self-attention runs as ring attention (``ops/ring_attention.py``):
  each chip holds one sequence block, KV rotates via ``ppermute`` over ICI,
  online softmax — O(L/N) memory per chip.
- Suffix attention needs the FULL prefix KV, which lives sharded across the
  ring. Rather than gathering it (which would defeat the sharding), every
  chip folds its own prefix-KV block into flash accumulators (m, l, acc)
  for the replicated suffix queries, and the partial accumulators are merged
  with a log-sum-exp ``pmax``/``psum`` — one joint softmax over
  [sharded prefix KV ; own causal suffix KV], numerically identical to the
  dense ``ops.attention.prefix_shared_attention``.

Weights still STREAM shard-by-shard (the framework's defining constraint):
the same ``ShardWeightSource`` feeds this scorer, with each shard's pytree
``device_put`` replicated over the mesh instead of onto one chip.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.ops import rms_norm
from flexible_llm_sharding_tpu.ops.attention import _local_clause, _softcap
from flexible_llm_sharding_tpu.ops.ring_attention import ring_decoder_layer
from flexible_llm_sharding_tpu.parallel.planner import plan_shards_dp
from flexible_llm_sharding_tpu.parallel.sharding import make_mesh
from flexible_llm_sharding_tpu.runtime.executor import (
    ShardWeightSource,
    _DTYPES,
    np_dtype_for,
)
from flexible_llm_sharding_tpu.runtime.tokenization import (
    PromptTokenizer,
    bucket_len,
    check_longrope_regime,
    longrope_total_len,
)
from flexible_llm_sharding_tpu.utils import checkpoint

Params = dict[str, Any]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_PRECISION = jax.lax.Precision.HIGHEST


def _partials(qr, k, v, mask, scale, softcap=None):
    """Flash accumulators of ``qr`` against one KV block.

    qr [S, Ls, n_kv, g, hd]; k/v [S?, Lk, n_kv, hd] or [Lk, n_kv, hd]
    (shared); mask broadcastable to [S, Ls, Lk]. Returns m, l
    [S, n_kv, g, Ls, 1] and acc [S, n_kv, g, Ls, hd], all fp32. ``softcap``
    (Gemma2) caps the scaled scores before the mask; tanh is monotone, so
    per-block capping commutes with the cross-block log-sum-exp merge.
    """
    shared = k.ndim == 3
    eq = "sqngh,knh->sngqk" if shared else "sqngh,sknh->sngqk"
    s = _softcap(
        jnp.einsum(eq, qr, k, precision=_PRECISION).astype(jnp.float32) * scale,
        softcap,
    )
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ev = "sngqk,knh->sngqh" if shared else "sngqk,sknh->sngqh"
    acc = jnp.einsum(ev, p.astype(v.dtype), v, precision=_PRECISION).astype(
        jnp.float32
    )
    return m, l, acc


def sharded_prefix_suffix_layer(
    params: Params,
    cfg: LlamaConfig,
    mesh: Mesh,
    axis: str,
    prefix_x: jax.Array,
    suffix_h: jax.Array,
    prefix_len: jax.Array,
    sliding: bool = False,
    rope_on: bool = True,
    return_kv: bool = False,
    total_len=None,
):
    """One decoder layer of the long-context scoring step.

    prefix_x [L, D] sharded over ``axis`` (L % mesh[axis] == 0);
    suffix_h [S, Ls, D] replicated; prefix_len int32 scalar (true length).
    Semantics match :func:`llama.prefix_suffix_layer` exactly — the suffix
    side sees one joint softmax over all real prefix keys plus its own
    causal keys at positions ``prefix_len + i``. The full family surface
    comes from the model library's own helpers (``position_qk``,
    ``_residual_attn``/``_residual_mlp``) plus scale/softcap/window/chunk in
    the partial-softmax masks; ``sliding``/``rope_on`` are this layer's
    STATIC flags.
    """
    s_cnt, ls, _ = suffix_h.shape
    eps = cfg.rms_norm_eps
    scale = cfg.attn_scale
    softcap = cfg.attn_logit_softcap
    window = cfg.sliding_window if sliding else None
    chunk = cfg.attention_chunk_size if sliding else None

    # --- prefix: ring attention layer, keeping its post-rope KV ---
    prefix_out, k_all, v_all = ring_decoder_layer(
        params, cfg, prefix_x, mesh, axis=axis, return_kv=True,
        sliding=sliding, rope_on=rope_on, total_len=total_len,
    )

    # --- suffix q/k/v at global positions prefix_len + i ---
    hs = rms_norm(suffix_h, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    pos_s = prefix_len + jnp.arange(ls)
    qs, ks, vs = llama.positioned_qkv(
        params, cfg, hs, pos_s, sliding, rope_on, total_len
    )

    n_kv = cfg.num_key_value_heads
    g = cfg.num_attention_heads // n_kv
    qr = qs.reshape(s_cnt, ls, n_kv, g, cfg.head_dim)

    # --- per-chip partial softmax over the local prefix-KV block, merged
    # with a log-sum-exp pmax/psum across the ring ---
    def local_partials(qr, k_blk, v_blk, plen):
        idx = jax.lax.axis_index(axis)
        lblk = k_blk.shape[0]
        kj = idx * lblk + jnp.arange(lblk)[None, None, :]  # global key pos
        vis = kj < plen
        if window is not None or chunk is not None:
            # Suffix query i sits at global position plen + i.
            qi = plen + jnp.arange(ls)[None, :, None]
            vis = _local_clause(vis, qi, kj, window, None, chunk)
        mask = jnp.broadcast_to(vis, (s_cnt, ls, lblk))
        m, l, acc = _partials(qr, k_blk, v_blk, mask, scale, softcap)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        return m_g, jax.lax.psum(l * corr, axis), jax.lax.psum(acc * corr, axis)

    rep = P()
    blk = P(axis, None, None)
    m_p, l_p, acc_p = jax.shard_map(
        local_partials,
        mesh=mesh,
        in_specs=(rep, blk, blk, rep),
        out_specs=(rep, rep, rep),
        check_vma=False,
    )(qr, k_all, v_all, prefix_len)

    # --- own suffix block: causal within the suffix; local clauses need the
    # absolute positions (the window's relative offsets cancel the plen
    # shift, the chunk boundaries do not) ---
    qi = jnp.arange(ls)[:, None]
    kj = jnp.arange(ls)[None, :]
    suffix_mask = kj <= qi
    if window is not None or chunk is not None:
        suffix_mask = _local_clause(
            suffix_mask, prefix_len + qi, prefix_len + kj, window, None, chunk
        )
    m_s, l_s, acc_s = _partials(qr, ks, vs, suffix_mask[None], scale, softcap)

    # --- merge the two accumulator sets (one joint softmax) ---
    m = jnp.maximum(m_p, m_s)
    cp, cs = jnp.exp(m_p - m), jnp.exp(m_s - m)
    l = l_p * cp + l_s * cs
    out = (acc_p * cp + acc_s * cs) / jnp.maximum(l, 1e-30)
    # [S, n_kv, g, Ls, hd_v] -> [S, Ls, n_q, hd_v] (V's own dim under MLA)
    attn_s = (
        out.transpose(0, 3, 1, 2, 4)
        .reshape(s_cnt, ls, n_kv * g, cfg.v_dim)
        .astype(suffix_h.dtype)
    )

    suffix_mid = llama._residual_attn(params, cfg, suffix_h, attn_s)
    suffix_out = llama._residual_mlp(params, cfg, suffix_mid)
    if return_kv:
        # Post-rope KV for the long-context KV-decode path: prefix KV stays
        # SHARDED over the sp mesh, suffix KV replicated.
        return prefix_out, suffix_out, {"kp": k_all, "vp": v_all, "ks": ks, "vs": vs}
    return prefix_out, suffix_out


def sharded_decode_layer(
    params: Params,
    cfg: LlamaConfig,
    mesh: Mesh,
    axis: str,
    x: jax.Array,
    kv: Params,
    prefix_len: jax.Array,
    suffix_eos: jax.Array,
    t: jax.Array,
    sliding: bool = False,
    rope_on: bool = True,
):
    """One decoder layer for ONE new token per suffix against cached KV
    whose PREFIX region is sharded over the sp mesh.

    The sequence-parallel analogue of :func:`llama.decode_step_layer`
    (semantics identical — one joint softmax over prefix/suffix/generated
    keys): each chip folds its own prefix-KV block into flash accumulators
    for the replicated single-token queries, the partials merge with a
    log-sum-exp pmax/psum, and the replicated suffix + generated regions
    fold in locally. x [S, 1, D] replicated; kv: {'kp','vp' [Lp, n_kv, hd]
    sp-sharded, 'ks','vs' [S, Ls, n_kv, hd], 'kg','vg' [S, T, n_kv, hd]
    replicated}; prefix_len/t int32 scalars; suffix_eos int32 [S].
    Returns (x_out, kv with slot t of kg/vg written).
    """
    s_cnt = x.shape[0]
    eps = cfg.rms_norm_eps
    scale = cfg.attn_scale
    softcap = cfg.attn_logit_softcap
    window = cfg.sliding_window if sliding else None
    chunk = cfg.attention_chunk_size if sliding else None

    h = rms_norm(x, params["input_layernorm"]["scale"], eps, cfg.norm_unit_offset)
    pos = (prefix_len + suffix_eos + 1 + t)[:, None]  # [S, 1]
    # longrope: per-suffix real length at this step; the decode runner's
    # check_longrope_regime guarantees the regime is constant per run.
    tl = pos[:, -1] + 1 if cfg.rope_scaling_kind == "longrope" else None
    q, k_new, v_new = llama.positioned_qkv(
        params, cfg, h, pos, sliding, rope_on, tl
    )  # [S, 1, n, qk_hd] / v_new [S, 1, n, v_dim] (distinct under MLA)

    kv = dict(kv)
    kv["kg"] = jax.lax.dynamic_update_slice_in_dim(kv["kg"], k_new, t, axis=1)
    kv["vg"] = jax.lax.dynamic_update_slice_in_dim(kv["vg"], v_new, t, axis=1)

    n_kv = cfg.num_key_value_heads
    g = cfg.num_attention_heads // n_kv
    qr = q.reshape(s_cnt, 1, n_kv, g, cfg.head_dim)
    q_abs = (prefix_len + suffix_eos + 1 + t)[:, None, None]  # [S, 1, 1]

    # --- sharded prefix region: per-chip partials, log-sum-exp merge ---
    def local_partials(qr, k_blk, v_blk, plen, q_abs):
        idx = jax.lax.axis_index(axis)
        lblk = k_blk.shape[0]
        kj = idx * lblk + jnp.arange(lblk)[None, None, :]  # global key pos
        vis = jnp.broadcast_to(kj < plen, (s_cnt, 1, lblk))
        if window is not None or chunk is not None:
            vis = _local_clause(vis, q_abs, kj, window, None, chunk)
        m, l, acc = _partials(qr, k_blk, v_blk, vis, scale, softcap)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        return m_g, jax.lax.psum(l * corr, axis), jax.lax.psum(acc * corr, axis)

    rep = P()
    blk = P(axis, None, None)
    m_p, l_p, acc_p = jax.shard_map(
        local_partials,
        mesh=mesh,
        in_specs=(rep, blk, blk, rep, rep),
        out_specs=(rep, rep, rep),
        check_vma=False,
    )(qr, kv["kp"], kv["vp"], prefix_len, q_abs)

    # --- own suffix region: keys j <= eos at absolute positions plen + j ---
    ls = kv["ks"].shape[1]
    kj = jnp.arange(ls)[None, None, :]
    vis = jnp.broadcast_to(kj <= suffix_eos[:, None, None], (s_cnt, 1, ls))
    if window is not None or chunk is not None:
        vis = _local_clause(vis, q_abs, prefix_len + kj, window, None, chunk)
    m_s, l_s, acc_s = _partials(qr, kv["ks"], kv["vs"], vis, scale, softcap)

    # --- generated region: keys j <= t at plen + eos + 1 + j ---
    tm = kv["kg"].shape[1]
    kj = jnp.arange(tm)[None, None, :]
    vis = jnp.broadcast_to(kj <= t, (s_cnt, 1, tm))
    if window is not None or chunk is not None:
        abs_k = prefix_len + suffix_eos[:, None, None] + 1 + kj
        vis = _local_clause(vis, q_abs, abs_k, window, None, chunk)
    m_g3, l_g3, acc_g3 = _partials(qr, kv["kg"], kv["vg"], vis, scale, softcap)

    # --- merge the three accumulator sets (one joint softmax) ---
    m = jnp.maximum(jnp.maximum(m_p, m_s), m_g3)
    cp, cs, cg = jnp.exp(m_p - m), jnp.exp(m_s - m), jnp.exp(m_g3 - m)
    l = l_p * cp + l_s * cs + l_g3 * cg
    out = (acc_p * cp + acc_s * cs + acc_g3 * cg) / jnp.maximum(l, 1e-30)
    # [S, n_kv, g, 1, hd_v] -> [S, 1, n_q, hd_v] (V's own dim under MLA)
    attn = (
        out.transpose(0, 3, 1, 2, 4)
        .reshape(s_cnt, 1, n_kv * g, cfg.v_dim)
        .astype(x.dtype)
    )
    mid = llama._residual_attn(params, cfg, x, attn)
    return llama._residual_mlp(params, cfg, mid), kv


class LongContextScorer:
    """Scores prompts whose prefix exceeds one chip's ``max_token_len``.

    One prompt at a time (suffixes batched): the prefix is sharded over an
    ``sp`` mesh of the visible chips, so the cap becomes
    ``n_chips * max_token_len``. Weights stream through the mesh
    shard-by-shard (replicated per shard) via the same ShardWeightSource as
    the single-chip executor.
    """

    def __init__(self, cfg: FrameworkConfig, devices=None, tokenizer=None):
        from flexible_llm_sharding_tpu.obs import trace as _trace
        from flexible_llm_sharding_tpu.obs.registry import (
            REGISTRY,
            weak_source,
        )

        _trace.ensure_configured(cfg)
        REGISTRY.register("longcontext", weak_source(self))
        self.cfg = cfg
        self.model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
        self.model_cfg.require_one_attention_shape("the long-context scorer")
        self.model_cfg.require_single_visit("the long-context scorer")
        devices = list(devices) if devices else None
        self.mesh = make_mesh(
            {"sp": len(devices)} if devices else None, devices=devices
        )
        self.sp = self.mesh.shape["sp"]
        self.dtype = _DTYPES[cfg.dtype]
        self.cap = self.sp * cfg.max_token_len
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        self.tokenizer = PromptTokenizer(
            tokenizer,
            max_token_len=self.cap,
            bucket_multiple=cfg.bucket_multiple * self.sp,
        )
        self.layer_names = checkpoint.layer_names_for(
            self.model_cfg.num_hidden_layers, tie_word_embeddings=False
        )
        self.plan = plan_shards_dp(len(self.layer_names), cfg.layer_num_per_shard)
        self._rep = NamedSharding(self.mesh, P())
        self._seq = NamedSharding(self.mesh, P("sp"))
        self._layer_fn = jax.jit(
            lambda params, px, sh, plen, sliding, rope_on, total_len=None: (
                sharded_prefix_suffix_layer(
                    params, self.model_cfg, self.mesh, "sp", px, sh, plen,
                    sliding=sliding, rope_on=rope_on, total_len=total_len,
                )
            ),
            # Static per-layer flags: at most four traces (local/global ×
            # rope/NoPE).
            static_argnums=(4, 5),
        )
        self.stats: dict[str, float] = {}

    def _layer_flags(self, seg: Params, i: int) -> tuple[bool, bool]:
        """(sliding, rope_on) for unstacked layer ``i`` of one decoders
        segment: the wrapper's per-layer flags (local/global mixes, llama4
        NoPE) when present, else uniform — every layer slides iff the config
        carries a local form, and rope is on."""
        flags, rflags = seg.get("sliding"), seg.get("rope")
        mc = self.model_cfg
        uniform = (
            mc.sliding_window is not None or mc.attention_chunk_size is not None
        )
        sliding = bool(np.asarray(flags)[i]) if flags is not None else uniform
        rope_on = bool(np.asarray(rflags)[i]) if rflags is not None else True
        return sliding, rope_on

    def _make_source(self, repeats: int) -> ShardWeightSource:
        """ONE weight source for a whole batch (shard list repeated
        ``repeats`` times): a cold source per pass would re-read the
        checkpoint with no prefetch overlap between passes."""
        from flexible_llm_sharding_tpu.faults.inject import FaultInjector
        from flexible_llm_sharding_tpu.runtime import hostcache, residency

        return ShardWeightSource(
            self.cfg.model_path,
            self.layer_names,
            list(self.plan.shards) * max(repeats, 1),
            np_dtype_for(self.cfg.dtype),
            device=self._rep,  # device_put accepts a Sharding: replicate
            prefetch_depth=self.cfg.effective_prefetch_depth(),
            tied_embeddings=self.model_cfg.tie_word_embeddings,
            layer_sliding=self.model_cfg.layer_sliding,
            layer_rope=self.model_cfg.layer_rope,
            retry_policy=self.cfg.retry_policy(),
            injector=FaultInjector.from_config(self.cfg.faults),
            verify_weights=self.cfg.verify_weights,
            # One source per batch = one sweep per prompt: prompt 2+ hits.
            host_cache=hostcache.cache_for(self.cfg),
            readahead_threads=self.cfg.readahead_threads,
            # Pins replicate over the sp mesh (placement_key keys on the
            # mesh's chips + spec, so a scorer rebuilt per batch reuses
            # the same resident copies instead of re-pinning).
            residency=residency.tier_for(
                self.cfg,
                self.layer_names,
                self.model_cfg.tie_word_embeddings,
                residency.probe_chip(self.mesh),
            ),
        )

    def __call__(self, prompts) -> list[np.ndarray]:
        t0 = time.perf_counter()
        prompts = list(prompts)
        source = self._make_source(len(prompts))
        stream = iter(source)
        try:
            out = [self._score_one(p, s, stream) for p, s in prompts]
        finally:
            source.close()
        self.stats = {
            "total_wall_s": time.perf_counter() - t0,
            "load_weights_time_s": source.load_time,
        }
        return out

    def _score_one(self, prefix: str, suffixes: tuple, stream) -> np.ndarray:
        t = self.tokenizer(prefix, suffixes)
        check_longrope_regime(self.model_cfg, [t])
        # The prefix bucket must split evenly over the ring.
        lp = bucket_len(
            len(t.prefix_ids), self.cfg.bucket_multiple * self.sp, self.cap
        )
        prefix_ids = np.full((lp,), self.tokenizer.pad_id, np.int32)
        prefix_ids[: len(t.prefix_ids)] = t.prefix_ids
        prefix_ids = jax.device_put(jnp.asarray(prefix_ids), self._seq)
        suffix_ids = jax.device_put(jnp.asarray(t.suffix_ids), self._rep)
        prefix_len = jnp.int32(t.prefix_len)
        suffix_eos = jax.device_put(jnp.asarray(t.suffix_eos), self._rep)
        total_len = longrope_total_len(
            self.model_cfg, t.prefix_len, t.suffix_eos[: t.num_suffixes]
        )

        prefix_x = suffix_h = scores = None
        for _ in range(len(self.plan.shards)):
            _, segments = next(stream)
            for kind, params in segments:
                if kind == "embed":
                    prefix_x = llama.embed(params, prefix_ids, self.dtype, self.model_cfg)
                    suffix_h = llama.embed(params, suffix_ids, self.dtype, self.model_cfg)
                elif kind == "decoders":
                    # Unstack the [k, ...] scan pytree: each layer runs as
                    # one jitted sharded step (shard_map inside); per-layer
                    # flags pick among the (at most four) traced variants.
                    stacked = params["layers"]
                    k_layers = jax.tree.leaves(stacked)[0].shape[0]
                    for i in range(k_layers):
                        layer = jax.tree.map(lambda a: a[i], stacked)
                        sliding, rope_on = self._layer_flags(params, i)
                        prefix_x, suffix_h = self._layer_fn(
                            layer, prefix_x, suffix_h, prefix_len, sliding,
                            rope_on, total_len,
                        )
                elif kind == "norm":
                    suffix_h = llama.select_eos_and_norm(
                        params, self.model_cfg, suffix_h, suffix_eos
                    )
                else:  # head
                    scores = np.asarray(
                        jax.device_get(
                            llama.lm_head_scores(
                                params,
                                suffix_h,
                                softcap=self.model_cfg.final_logit_softcap,
                            )
                        )
                    )
        return np.expand_dims(scores[: t.num_suffixes], axis=1)


class LongContextDecoder(LongContextScorer):
    """KV-cache decode for prompts whose prefix exceeds one chip's cap.

    Composes the framework's two headline extensions: long context (the sp
    mesh, where the reference truncates) and KV-cache generation (where the
    reference re-runs the whole prompt per token). The prefill pass is the
    scorer's sharded forward, additionally parking every layer's KV — the
    prefix region stays SHARDED over the mesh, suffix/generated regions
    replicated — and each decode step streams the weights once more, runs
    :func:`sharded_decode_layer` per layer (one new token per suffix), and
    scores through norm + lm_head. Greedy, token-id append semantics
    (matches ``runtime/decode.py DecodeGenerator``).
    """

    def __init__(self, cfg: FrameworkConfig, devices=None, tokenizer=None):
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        super().__init__(cfg, devices=devices, tokenizer=tokenizer)
        self.raw_tokenizer = tokenizer
        self._prefill_fn = jax.jit(
            lambda params, px, sh, plen, sliding, rope_on, total_len=None: (
                sharded_prefix_suffix_layer(
                    params, self.model_cfg, self.mesh, "sp", px, sh, plen,
                    sliding=sliding, rope_on=rope_on, return_kv=True,
                    total_len=total_len,
                )
            ),
            static_argnums=(4, 5),
        )
        self._decode_fn = jax.jit(
            lambda params, x, kv, plen, eos, tt, sliding, rope_on: (
                sharded_decode_layer(
                    params, self.model_cfg, self.mesh, "sp", x, kv, plen,
                    eos, tt, sliding=sliding, rope_on=rope_on,
                )
            ),
            static_argnums=(6, 7),
            # The caller overwrites kv_layers[li] with the result, so the
            # old cache (incl. the sp-sharded prefix KV — the big buffer on
            # exactly this path) updates in place instead of copying per
            # layer per token.
            donate_argnums=(2,),
        )

    def __call__(self, prompts):
        """Returns (scores, updated_prompts, tokens_processed) — the
        ``orchestration.run_decode`` contract. scores[i]: float32
        [n_suffixes, num_gen_token, vocab]."""
        t0 = time.perf_counter()
        prompts = list(prompts)
        n_gen = max(self.cfg.num_gen_token, 1)
        # Prefill + (n_gen - 1) decode streams per prompt, in order.
        source = self._make_source(max(len(prompts), 1) * n_gen)
        stream = iter(source)
        scores_out, updated, tokens = [], [], 0.0
        # Greedy argmax (default) or temperature/top-k/top-p sampling via
        # the shared picker; ONE rng for the batch (deterministic per
        # cfg.seed; dists here are already sliced to real suffixes). Scores
        # stay the raw distributions either way.
        from flexible_llm_sharding_tpu.runtime.generation import make_picker

        pick = make_picker(self.cfg)
        try:
            for prefix, suffixes in prompts:
                dists, hist, tp = self._generate_one(
                    prefix, suffixes, stream, n_gen, pick
                )
                scores_out.append(dists)
                updated.append(
                    (
                        prefix,
                        tuple(
                            s + self.raw_tokenizer.decode(hist[s_i])
                            for s_i, s in enumerate(suffixes)
                        ),
                    )
                )
                tokens += tp
        finally:
            source.close()
        self.stats = {
            "total_wall_s": time.perf_counter() - t0,
            "load_weights_time_s": source.load_time,
            "tokens_processed": tokens,
        }
        return scores_out, updated, int(tokens)

    def _generate_one(
        self, prefix: str, suffixes: tuple, stream, n_gen: int, pick
    ):
        t = self.tokenizer(prefix, suffixes)
        # Fed positions must not cross the longrope boundary: parked
        # (sp-sharded) prefix KV can't be re-rotated mid-generation. The
        # last generated token is never fed back, hence n_gen - 1.
        check_longrope_regime(self.model_cfg, [t], extra_len=max(n_gen - 1, 0))
        lp = bucket_len(
            len(t.prefix_ids), self.cfg.bucket_multiple * self.sp, self.cap
        )
        prefix_ids = np.full((lp,), self.tokenizer.pad_id, np.int32)
        prefix_ids[: len(t.prefix_ids)] = t.prefix_ids
        prefix_ids = jax.device_put(jnp.asarray(prefix_ids), self._seq)
        suffix_ids = jax.device_put(jnp.asarray(t.suffix_ids), self._rep)
        prefix_len = jnp.int32(t.prefix_len)
        suffix_eos = jax.device_put(jnp.asarray(t.suffix_eos), self._rep)
        total_len = longrope_total_len(
            self.model_cfg, t.prefix_len, t.suffix_eos[: t.num_suffixes]
        )
        s_cnt = t.suffix_ids.shape[0]

        kv_layers: list[Params] = []
        dists: list[np.ndarray] = []  # per-step [S_true, V]

        # --- prefill: sharded forward, parking per-layer KV ---------------
        prefix_x = suffix_h = None
        for _ in range(len(self.plan.shards)):
            _, segments = next(stream)
            for kind, params in segments:
                if kind == "embed":
                    prefix_x = llama.embed(params, prefix_ids, self.dtype, self.model_cfg)
                    suffix_h = llama.embed(params, suffix_ids, self.dtype, self.model_cfg)
                elif kind == "decoders":
                    stacked = params["layers"]
                    k_layers = jax.tree.leaves(stacked)[0].shape[0]
                    for i in range(k_layers):
                        layer = jax.tree.map(lambda a: a[i], stacked)
                        sliding, rope_on = self._layer_flags(params, i)
                        prefix_x, suffix_h, kv = self._prefill_fn(
                            layer, prefix_x, suffix_h, prefix_len, sliding,
                            rope_on, total_len,
                        )
                        # Head count/dims from the layer's own parked KV
                        # (MLA: n_kv == n_heads, v_head_dim != qk dim).
                        slots = max(1, n_gen - 1)
                        kv_layers.append(
                            kv
                            | {
                                "kg": jax.device_put(
                                    jnp.zeros(
                                        (s_cnt, slots, *kv["ks"].shape[-2:]),
                                        self.dtype,
                                    ),
                                    self._rep,
                                ),
                                "vg": jax.device_put(
                                    jnp.zeros(
                                        (s_cnt, slots, *kv["vs"].shape[-2:]),
                                        self.dtype,
                                    ),
                                    self._rep,
                                ),
                            }
                        )
                elif kind == "norm":
                    suffix_h = llama.select_eos_and_norm(
                        params, self.model_cfg, suffix_h, suffix_eos
                    )
                else:  # head
                    dists.append(
                        np.asarray(
                            jax.device_get(
                                llama.lm_head_scores(
                                    params,
                                    suffix_h,
                                    softcap=self.model_cfg.final_logit_softcap,
                                )
                            )
                        )[: t.num_suffixes]
                    )

        # --- decode steps: one token per suffix per stream ----------------
        hist_rows = [pick(dists[-1])]  # [S_true] per emitted step
        for step in range(n_gen - 1):
            last = hist_rows[-1]  # [S_true]
            ids = np.full((s_cnt, 1), int(last[0]) if len(last) else 0, np.int64)
            ids[: t.num_suffixes, 0] = last
            ids = jax.device_put(jnp.asarray(ids), self._rep)
            x = None
            norm_params = None
            li = 0
            for _ in range(len(self.plan.shards)):
                _, segments = next(stream)
                for kind, params in segments:
                    if kind == "embed":
                        x = llama.embed(params, ids, self.dtype, self.model_cfg)
                    elif kind == "decoders":
                        stacked = params["layers"]
                        k_layers = jax.tree.leaves(stacked)[0].shape[0]
                        for i in range(k_layers):
                            layer = jax.tree.map(lambda a: a[i], stacked)
                            sliding, rope_on = self._layer_flags(params, i)
                            x, kv_layers[li] = self._decode_fn(
                                layer, x, kv_layers[li], prefix_len,
                                suffix_eos, jnp.int32(step), sliding, rope_on,
                            )
                            li += 1
                    elif kind == "norm":
                        norm_params = params
                    else:  # head
                        normed = rms_norm(
                            x,
                            norm_params["scale"],
                            self.model_cfg.rms_norm_eps,
                            self.model_cfg.norm_unit_offset,
                        )
                        dists.append(
                            np.asarray(
                                jax.device_get(
                                    llama.lm_head_scores(
                                        params,
                                        normed,
                                        softcap=self.model_cfg.final_logit_softcap,
                                    )
                                )
                            )[: t.num_suffixes]
                        )
            hist_rows.append(pick(dists[-1]))

        hist = np.stack(hist_rows, axis=1)  # [S, n_gen]
        scores = np.stack(dists, axis=1)  # [S_true, n_gen, V]
        tokens = float(
            t.tokens_processed + t.num_suffixes * max(n_gen - 1, 0)
        )
        return scores, hist, tokens


def prefix_token_count(tokenizer, prefix: str) -> int:
    """Untruncated prefix token count — the long-context routing predicate."""
    return len(tokenizer(prefix)["input_ids"])


__all__ = [
    "LongContextScorer",
    "LongContextDecoder",
    "sharded_prefix_suffix_layer",
    "sharded_decode_layer",
    "prefix_token_count",
]
