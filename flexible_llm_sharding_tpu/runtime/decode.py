"""KV-cache decode mode: fast multi-token generation for the streaming executor.

The reference's generation loop re-runs the ENTIRE sharded forward per new
token — full re-tokenisation, full prompt recompute through every layer
(``/root/reference/main.py:65-76``; SURVEY.md §3.5 calls it the known scaling
cliff: per-token cost == full-prompt cost). This module removes the compute
half of that cliff while keeping the framework's defining constraint (weights
stream through the chip shard-by-shard, HBM holds only one shard):

- **Prefill** runs the normal streaming pass once, but each decoder layer
  additionally emits its post-RoPE KV, which is parked per (shard, block) in
  host RAM (or HBM with ``storage_location='tpu'``).
- **Each decode step** re-streams the weights (that is the point of the
  design) but computes only ONE token per suffix per layer against the cached
  KV — O(1) sequence work instead of O(prefix+suffix).

Semantics note: the reference rebuilds suffix STRINGS per token
(argmax -> ``tokenizer.decode`` -> re-encode, ``/root/reference/main.py:85-90``),
which can re-tokenise differently; this mode appends token IDS directly.
Greedy token choices match token-level greedy decoding exactly (tested
against the monolithic oracle); the ``_updated.pkl`` text is produced by
decoding the id history. Use the default (slow) loop for bit-exact reference
string semantics.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from flexible_llm_sharding_tpu.adapters.apply import lora_shift
from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.obs import trace as obs_trace
from flexible_llm_sharding_tpu.parallel.planner import plan_shards_dp
from flexible_llm_sharding_tpu.runtime.executor import (
    ShardWeightSource,
    _embed_block,
    _norm_block,
    _head_block,
    np_dtype_for,
    _DTYPES,
)
from flexible_llm_sharding_tpu.runtime.tokenization import (
    PromptTokenizer,
    check_longrope_regime,
    longrope_total_len,
    make_blocks,
)
from flexible_llm_sharding_tpu.utils import checkpoint

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Jitted blocks (module-level: shared jit cache)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4, 5))
def _prefill_decoders(
    cfg: LlamaConfig, use_pallas, tp_mesh, seg, prefix_h, suffix_h, prefix_len,
    total_len=None, delta=None,
):
    """Scan k layers over a block, emitting per-layer KV as scan outputs.

    seg: {"layers": [k, ...] pytree, "sliding": bool [k] or None,
    "rope": bool [k] or None (llama4 NoPE flags)}.
    Returns (prefix_h, suffix_h, kv) with kv leaves shaped [k, B, ...].
    ``total_len`` int32 [B]: longrope's per-prompt real-length selector.
    ``delta``: optional multi-adapter LoRA shift (adapters/apply.py) —
    {"A": [k, G, D, R], "B": [k, G, R, D], "g": [B], "scale": [G]};
    applied to both hidden streams at each layer's ENTRY. ``None`` keeps
    the traced computation byte-identical to a tree without adapters
    (the branch is Python-level, resolved at trace time).
    """
    stacked, flags, rflags = seg["layers"], seg["sliding"], seg.get("rope")
    xs_in = (
        (stacked, flags, rflags)
        if delta is None
        else (stacked, flags, rflags, delta["A"], delta["B"])
    )

    def body(carry, xs):
        if delta is None:
            layer_params, sliding, rope_on = xs
        else:
            layer_params, sliding, rope_on, d_a, d_b = xs
        p, s = carry
        if delta is not None:
            p = lora_shift(p, d_a, d_b, delta["g"], delta["scale"])
            s = lora_shift(s, d_a, d_b, delta["g"], delta["scale"])

        def one_layer(lp_, c_, p_, s_, plen_, tlen_):
            return llama.prefix_suffix_layer(
                lp_, c_, p_, s_, plen_,
                use_pallas=use_pallas,
                return_kv=True,
                sliding=sliding,
                rope_on=rope_on,
                tp_mesh=tp_mesh,
                total_len=tlen_,
            )

        step = jax.vmap(
            one_layer,
            in_axes=(None, None, 0, 0, 0, 0 if total_len is not None else None),
        )
        with jax.named_scope("decoder_layer"):
            p, s, kv = step(layer_params, cfg, p, s, prefix_len, total_len)
        return (p, s), kv

    (prefix_h, suffix_h), kv = jax.lax.scan(
        body, (prefix_h, suffix_h), xs_in
    )
    return prefix_h, suffix_h, kv


@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(5,))
def _suffix_prefill_decoders(
    cfg: LlamaConfig, use_pallas, tp_mesh, seg, kv_p, suffix_h, prefix_len,
    total_len=None, delta=None,
):
    """Suffix-only prefill scan over a block, fed POOLED prefix KV.

    The cross-wave reuse path (runtime/kvpool.py): when a sealed prefix
    entry already holds this segment's post-RoPE (kp, vp), only the suffix
    half of each layer runs (llama.suffix_only_layer) — bit-identical to
    _prefill_decoders' suffix stream, with zero prefix compute.

    kv_p: {"kp": [k, B, Lp, n_kv, hd], "vp": [k, B, Lp, n_kv, v_dim]} —
    NOT donated; the caller re-attaches these leaves to the decode-KV dict.
    Returns (suffix_h, {"ks","vs"} with leaves shaped [k, B, ...]).
    ``delta``: the optional multi-adapter LoRA shift (see
    ``_prefill_decoders``) applied to the suffix stream at layer entry —
    bit-identical to the full-prefill path's suffix stream, because the
    pooled prefix KV it reuses was itself produced under the SAME
    adapter's shift (the KV pool keys fold in the adapter id).
    """
    stacked, flags, rflags = seg["layers"], seg["sliding"], seg.get("rope")
    xs_in = (
        (stacked, flags, rflags, kv_p["kp"], kv_p["vp"])
        if delta is None
        else (
            stacked, flags, rflags, kv_p["kp"], kv_p["vp"],
            delta["A"], delta["B"],
        )
    )

    def body(s, xs):
        if delta is None:
            layer_params, sliding, rope_on, kp_l, vp_l = xs
        else:
            layer_params, sliding, rope_on, kp_l, vp_l, d_a, d_b = xs
            s = lora_shift(s, d_a, d_b, delta["g"], delta["scale"])

        def one_layer(lp_, c_, kp_, vp_, s_, plen_, tlen_):
            return llama.suffix_only_layer(
                lp_, c_, kp_, vp_, s_, plen_,
                use_pallas=use_pallas,
                sliding=sliding,
                rope_on=rope_on,
                tp_mesh=tp_mesh,
                total_len=tlen_,
            )

        step = jax.vmap(
            one_layer,
            in_axes=(None, None, 0, 0, 0, 0, 0 if total_len is not None else None),
        )
        with jax.named_scope("decoder_layer"):
            s, kv_s = step(layer_params, cfg, kp_l, vp_l, s, prefix_len, total_len)
        return s, kv_s

    suffix_h, kv_s = jax.lax.scan(body, suffix_h, xs_in)
    return suffix_h, kv_s


def _decode_decoders_impl(
    cfg: LlamaConfig,
    use_pallas,
    tp_mesh,
    seg,
    kv,
    x,
    prefix_len,
    suffix_eos,
    t,
    gen_only: bool = False,
    t_in_axis=None,
    delta=None,
):
    """Scan k layers' decode over a block (K newest tokens per suffix).

    seg: {"layers": [k, ...] pytree, "sliding": bool [k] or None,
    "rope": bool [k] or None};
    kv: pytree with leaves [k, B, ...] (kg/vg slots < t filled); x [B, S, K, D];
    prefix_len [B]; suffix_eos [B, S]; t: scalar slot (plain decode,
    ``t_in_axis=None``) or [B, S] per-suffix slot offsets (speculative
    passes, ``t_in_axis=0``). Returns (x, kv with slots t..t+K-1 updated).
    ``gen_only`` (static) returns only the mutated {'kg','vg'} leaves as the
    scan's stacked output — the fused step path uses it so the read-only
    prefix/suffix KV is never re-materialised by the layer scan.
    ``delta``: the optional multi-adapter LoRA shift (see
    ``_prefill_decoders``) applied to ``x`` at each layer's entry.
    """
    stacked, flags, rflags = seg["layers"], seg["sliding"], seg.get("rope")
    xs_in = (
        (stacked, flags, rflags, kv)
        if delta is None
        else (stacked, flags, rflags, kv, delta["A"], delta["B"])
    )

    def body(x, layer):
        if delta is None:
            layer_params, sliding, rope_on, layer_kv = layer
        else:
            layer_params, sliding, rope_on, layer_kv, d_a, d_b = layer
            x = lora_shift(x, d_a, d_b, delta["g"], delta["scale"])
        step = jax.vmap(
            partial(
                llama.decode_step_layer,
                sliding=sliding,
                rope_on=rope_on,
                use_pallas=use_pallas,
                tp_mesh=tp_mesh,
            ),
            in_axes=(None, None, 0, 0, 0, 0, t_in_axis),
        )
        with jax.named_scope("decoder_layer"):
            x, layer_kv = step(layer_params, cfg, x, layer_kv, prefix_len, suffix_eos, t)
        if gen_only:
            layer_kv = {"kg": layer_kv["kg"], "vg": layer_kv["vg"]}
        return x, layer_kv

    x, kv = jax.lax.scan(body, x, xs_in)
    return x, kv


# Per-step jitted form (the streaming / sampling decode loop): kv and x are
# donated — each step reuses the previous buffers.
_decode_decoders = jax.jit(
    _decode_decoders_impl, static_argnums=(0, 1, 2), donate_argnums=(4, 5)
)


def _decode_norm_head_impl(cfg: LlamaConfig, norm_params, head_params, x):
    """x [B, S, 1, D] -> float32 next-token distributions [B, S, V]."""
    from flexible_llm_sharding_tpu.ops import rms_norm

    with jax.named_scope("final_norm"):
        h = rms_norm(
            x, norm_params["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset
        )
    with jax.named_scope("lm_head"):
        return jax.vmap(
            partial(llama.lm_head_scores, softcap=cfg.final_logit_softcap),
            in_axes=(None, 0),
        )(head_params, h)


_decode_norm_head = jax.jit(_decode_norm_head_impl, static_argnums=(0,))


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), donate_argnums=(7,))
def _fused_decode_steps(
    cfg: LlamaConfig,
    use_pallas,
    tp_mesh,
    n_steps: int,
    dtype,
    segs,
    kv_static,
    kv_gen,
    embed_params,
    norm_params,
    head_params,
    init_ids,
    prefix_len,
    suffix_eos,
):
    """ALL greedy decode steps for one block as ONE XLA program.

    When the weights are resident (DecodeGenerator._resident) and selection
    is greedy, the per-step Python loop — one jitted dispatch per shard per
    step plus a host round-trip per token pick — is pure overhead: every
    dispatch crosses the host->device link,
    and the KV pytrees bounce host<->HBM when the store is host-resident.
    This fuses the whole generation into one ``lax.scan`` over steps: embed
    the previous pick, run every decoder segment's layer scan (KV slot ``t``
    updated in place via donation), norm+head, and pick the next token with
    an ON-DEVICE argmax (bitwise the same winner as the host ``np.argmax``
    both paths take on ties: first index of the float32 max).

    The reference re-runs its entire sharded forward per token from Python
    (``/root/reference/main.py:63-90``); this is the opposite end of the
    design space — zero host involvement between tokens.

    segs: tuple of decoder segments (each ``{"layers", "sliding", "rope"}``)
    in layer order. The KV splits by mutability so the scan carries only
    what changes: ``kv_static`` (per-segment {'kp','vp','ks','vs'}) is
    closed over — one copy for the whole program — while ``kv_gen``
    (per-segment {'kg','vg'}, donated) threads through the carry and is
    updated at slot ``t`` each step. init_ids [B, S] = prefill's pick.
    Returns (dists [n_steps, B, S, V] float32, toks [n_steps, B, S]).
    """

    def one_step(carry, t):
        ids, gens = carry
        x = llama.embed(embed_params, ids[..., None], dtype, cfg)
        new_gens = []
        for seg, stat, gen in zip(segs, kv_static, gens):
            x, gen = _decode_decoders_impl(
                cfg, use_pallas, tp_mesh, seg, {**stat, **gen}, x,
                prefix_len, suffix_eos, t, gen_only=True,
            )
            new_gens.append(gen)
        dist = _decode_norm_head_impl(cfg, norm_params, head_params, x)
        ids_next = jnp.argmax(dist, axis=-1).astype(jnp.int32)
        return (ids_next, tuple(new_gens)), (dist, ids_next)

    (_, _), (dists, toks) = jax.lax.scan(
        one_step,
        (jnp.asarray(init_ids, jnp.int32), kv_gen),
        jnp.arange(n_steps, dtype=jnp.int32),
    )
    return dists, toks


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3, 4))
def _spec_decoders(
    cfg: LlamaConfig, tp_mesh, seg, kv, x, prefix_len, suffix_eos, base,
    delta=None,
):
    """Scan k layers' K-token speculative verify step over a block.

    x [B, S, K, D] — the last accepted token plus K-1 drafts per suffix;
    base [B, S] — each suffix's own generated-KV slot offset (suffixes
    accept different counts per pass, so their slot clocks drift apart).
    Always the XLA decode op (the flash decode kernel is single-token);
    same layer scan as the plain per-step path, with the slot arg vmapped
    over the batch instead of broadcast.
    """
    return _decode_decoders_impl(
        cfg, False, tp_mesh, seg, kv, x, prefix_len, suffix_eos, base,
        t_in_axis=0, delta=delta,
    )


@partial(jax.jit, static_argnums=(0,))
def _spec_norm_head(cfg: LlamaConfig, norm_params, head_params, x):
    """x [B, S, K, D] -> float32 distributions [B, S, K, V] (every fed
    position scored — position j's distribution verifies draft j+1)."""
    from flexible_llm_sharding_tpu.ops import rms_norm

    with jax.named_scope("final_norm"):
        h = rms_norm(
            x, norm_params["scale"], cfg.rms_norm_eps, cfg.norm_unit_offset
        )
    with jax.named_scope("lm_head"):
        return llama.lm_head_scores_multi(
            head_params, h, softcap=cfg.final_logit_softcap
        )


# propose_draft scans at most this many trailing tokens of each haystack
# (own context and each sibling-corpus pool). Without the cap the sweep
# over sliding_window_view is O(context) per suffix per pass — a long
# context rescans its whole token history every step for a draft whose
# useful matches are overwhelmingly recent (the lookup wants the LAST
# occurrence anyway). Bounding the scan to the trailing window keeps the
# per-pass draft cost constant; behavior is identical whenever the
# sequence fits the window (pinned by tests/test_spec_serve.py), and on
# longer histories only matches older than the window are forgone —
# a draft-quality change only, never a correctness one (verification is
# draft-agnostic).
DRAFT_SCAN_WINDOW = 512


def propose_draft(context_ids, k: int, ngram: int = 2, corpus=None):
    """Prompt-lookup drafting (public technique — Saxena's prompt lookup
    decoding / HF assisted generation's n-gram candidate source): find the
    LAST earlier occurrence of the context's final n-gram and propose the
    tokens that followed it. No draft model, no extra memory — the draft
    quality rides the input-grounded nature of the workload (the reference's
    continuation-scoring prompts repeat prompt phrases constantly).

    ``corpus`` (optional): extra id sequences to fall back to when the
    request's own context has no match — the verifier passes the SIBLING
    suffixes' contexts of the same prompt. The paper's workload scores
    several continuations of one prefix, and their greedy chains converge
    to the same attractor, so a cycle one suffix has already entered
    predicts a sibling that is entering it — crucial when the model's
    generated tokens never appear in the prompt itself (then self-lookup
    has nothing to match until the suffix's OWN history repeats).
    Soundness is free: verification is draft-agnostic, any source keeps
    greedy-exact output and only changes acceptance.

    Returns EXACTLY ``k`` draft ids (the verify step needs static shapes);
    when no match or continuation exists it pads by repeating the last
    token — bad drafts cost nothing but rejected slots.
    """
    ids = np.asarray(context_ids, np.int64)
    pools = [np.asarray(c, np.int64) for c in (corpus or ())]
    n = len(ids)
    draft: list[int] = []
    for g in range(min(ngram, n - 1), 0, -1):
        tail = ids[n - g :]
        # Own context first (most relevant), then each sibling pool. The
        # own-context haystack excludes the tail's own position; a pool is
        # a whole foreign sequence, so every window of it is "earlier".
        for hay, pool in [(ids[: n - 1], ids)] + [(p, p) for p in pools]:
            if len(hay) < g:
                continue
            # Bounded match window: scan only the trailing
            # DRAFT_SCAN_WINDOW tokens; ``off`` maps window-relative hit
            # positions back into the pool for the continuation slice.
            off = max(0, len(hay) - DRAFT_SCAN_WINDOW)
            win = np.lib.stride_tricks.sliding_window_view(hay[off:], g)
            hits = np.flatnonzero((win == tail[None, :]).all(axis=1))
            # Last match with a nonempty continuation (a pool match at the
            # pool's very end proposes nothing).
            for start in hits[::-1]:
                start = off + int(start)
                cont = pool[int(start) + g : int(start) + g + k]
                if len(cont):
                    draft = [int(c) for c in cont]
                    break
            if draft:
                break
        if draft:
            break
    while len(draft) < k:
        draft.append(int(draft[-1] if draft else ids[-1]))
    return np.asarray(draft[:k], np.int64)


def draft_contexts(tps, t0):
    """[B][S] initial draft contexts for one block: real prefix + real
    suffix + the first picked token, per tokenized prompt ``tps[r]`` and
    prefill picks ``t0`` [B, S]. ONE construction rule shared by the
    offline DecodeGenerator (one prompt per row) and the serving engine
    (one wave entry per row; a resumed request's generated-so-far tokens
    are already folded into its suffix ids, so they ride the context) —
    the context contract cannot drift between the two paths."""
    return [
        [
            np.concatenate(
                [
                    tp.prefix_ids[: tp.prefix_len],
                    tp.suffix_ids[s][: int(tp.suffix_eos[s]) + 1],
                    [int(t0[r, s])],
                ]
            )
            for s in range(tp.suffix_ids.shape[0])
        ]
        for r, tp in enumerate(tps)
    ]


class SpecVerifier:
    """The K+1-slot batch-verification state machine for ONE block — the
    shared core of speculative decoding, used by the offline
    ``DecodeGenerator`` loop and the serving engine's per-wave verify
    passes (``serve/engine.py``).

    Each pass feeds, per suffix, the last accepted token plus ``spec_k``
    drafts through ONE weight sweep (``_spec_decoders`` +
    ``_spec_norm_head``), then accepts the longest draft prefix matching
    the greedy argmax chain and emits 1..K+1 tokens. Per-suffix
    acceptance differs, so each suffix keeps its own generated-KV slot
    clock (``g`` - 1 is the base offset the next pass writes from) —
    the slot-clock drift the verify kernel vmaps over. Output is
    greedy-exact: position j's argmax is exactly what sequential greedy
    would emit after the accepted prefix, whatever the drafts were.

    State per suffix: the emitted distribution/token histories (ragged —
    suffixes advance at different rates), the draft context (prefix +
    suffix + emitted ids; serve folds preemption-resume tokens into the
    suffix ids BEFORE construction, so resumed work is never re-drafted
    stale), and the per-suffix budget (total picks including the
    prefill's). Inactive rows (bucket padding) are frozen at budget with
    constant histories: they never gate ``done``, draft, or count stats.
    """

    def __init__(
        self, spec_k: int, draft_fn, contexts, budgets, init_dist,
        init_toks, active=None,
    ):
        # contexts: [B][S] int arrays, each ending with the first picked
        # token; budgets: int [B, S]; init_dist: [B, S, V] float32 (the
        # prefill head's distributions); init_toks: [B, S] picked ids;
        # active: [B][S] bools (None = all rows real).
        import inspect

        self.k = spec_k
        self._draft = draft_fn if draft_fn is not None else propose_draft
        try:
            self._corpus_ok = (
                "corpus" in inspect.signature(self._draft).parameters
            )
        except (TypeError, ValueError):
            self._corpus_ok = False
        self.budgets = np.asarray(budgets, np.int64)
        bsz, s_b = self.budgets.shape
        self.active = (
            np.asarray(active, bool)
            if active is not None
            else np.ones((bsz, s_b), bool)
        )
        self.ctx = [[np.asarray(contexts[r][s], np.int64) for s in range(s_b)]
                    for r in range(bsz)]
        self.g = np.ones((bsz, s_b), np.int64)
        self.hist_d = [
            [[init_dist[r, s]] for s in range(s_b)] for r in range(bsz)
        ]
        self.hist_t = [
            [[int(init_toks[r, s])] for s in range(s_b)] for r in range(bsz)
        ]
        self.drafted = 0
        self.accepted = 0
        self.rejected = 0
        self.passes = 0
        for r in range(bsz):
            for s in range(s_b):
                if not self.active[r, s]:
                    # Padding rows: frozen at budget with constant
                    # histories (their text is discarded; the constant
                    # fill keeps step-major reshapes rectangular).
                    bud = int(self.budgets[r, s])
                    self.g[r, s] = bud
                    self.hist_d[r][s] = [init_dist[r, s]] * bud
                    self.hist_t[r][s] = [int(init_toks[r, s])] * bud
        self._fed = self._drafts = self._base = None
        # Per-pass per-row draft-request widths (None = every row drafts
        # the full ``spec_k``) and the matching per-row accounting deltas
        # of the latest finished pass — the serve engine's per-SLO-class
        # counter split reads these instead of diffing the totals.
        self._pass_k = None
        self.last_drafted = np.zeros((bsz, s_b), np.int64)
        self.last_accepted = np.zeros((bsz, s_b), np.int64)

    def set_pass_k(self, karr) -> None:
        """Cap the next passes' per-row draft requests at ``karr`` [B, S]
        (clipped to [0, spec_k]; None restores the uniform default). The
        fed window stays K+1 wide — static shapes, one compile — but a
        row capped at ``k_use`` only drafts/verifies its first ``k_use``
        slots; at 0 it requests no drafts at all (one token per pass,
        the plain-path cadence). Acceptance accounting counts only the
        requested slots, so an adaptive controller's signal is never
        polluted by slots it chose not to spend."""
        if karr is None:
            self._pass_k = None
            return
        self._pass_k = np.clip(
            np.asarray(karr, np.int64), 0, self.k
        ).reshape(self.g.shape)

    def _k_use(self, r: int, s: int) -> int:
        return self.k if self._pass_k is None else int(self._pass_k[r, s])

    @property
    def done(self) -> bool:
        return bool((self.g >= self.budgets).all())

    def emitted(self, r: int, s: int) -> int:
        """Tokens emitted so far for one suffix (incl. the prefill's)."""
        return int(self.g[r, s])

    def stats(self) -> dict[str, int]:
        """Draft-economy counters (the serve metrics' spec family reads
        per-pass deltas; this snapshot serves tests/debugging)."""
        return {
            "passes": self.passes,
            "drafted": self.drafted,
            "accepted": self.accepted,
            "rejected": self.rejected,
        }

    def begin_pass(self):
        """Fix this pass's fed tokens and per-suffix slot offsets BEFORE
        the weight sweep: (fed [B, S, K+1] int64, base [B, S] int32).
        Per-request draft streams: each unfinished suffix drafts over its
        own context via ``draft_fn``, with the sibling suffixes' contexts
        as a fallback corpus when the draft source accepts one."""
        k1 = self.k + 1
        bsz, s_b = self.g.shape
        fed = np.zeros((bsz, s_b, k1), np.int64)
        drafts = np.zeros((bsz, s_b, self.k), np.int64)
        for r in range(bsz):
            for s in range(s_b):
                fed[r, s, 0] = self.hist_t[r][s][-1]
                # Draft only when an accepted token could still be
                # emitted (remaining > 1): at remaining == 1 the pass
                # emits exactly picks[0] whatever rides the draft slots.
                k_use = self._k_use(r, s)
                if k_use > 0 and self.budgets[r, s] - self.g[r, s] > 1:
                    if self._corpus_ok:
                        sib = [
                            self.ctx[r][j]
                            for j in range(s_b)
                            if j != s and self.active[r, j]
                        ]
                        drafts[r, s, :k_use] = self._draft(
                            self.ctx[r][s], k_use, corpus=sib
                        )
                    else:
                        drafts[r, s, :k_use] = self._draft(
                            self.ctx[r][s], k_use
                        )
        fed[:, :, 1:] = drafts
        self._fed, self._drafts = fed, drafts
        self._base = (self.g - 1).astype(np.int32)
        return fed, self._base

    def finish_pass(self, dist: np.ndarray) -> np.ndarray:
        """Accept against the verify head's ``dist`` [B, S, K+1, V]:
        longest draft prefix matching the argmax chain, plus the one
        token the pass always yields. Returns tokens emitted per suffix
        this pass ([B, S] int). Stats count only USEFUL draft slots
        (at most remaining-1 drafts can become emissions)."""
        assert self._drafts is not None, "finish_pass without begin_pass"
        self.passes += 1
        picks = np.argmax(dist, axis=-1)  # [B, S, K+1]
        bsz, s_b = self.g.shape
        emitted = np.zeros((bsz, s_b), np.int64)
        self.last_drafted.fill(0)
        self.last_accepted.fill(0)
        for r in range(bsz):
            for s in range(s_b):
                if self.g[r, s] >= self.budgets[r, s]:
                    continue
                k_use = self._k_use(r, s)
                a = 0
                while (
                    a < k_use
                    and picks[r, s, a] == self._drafts[r, s, a]
                ):
                    a += 1
                remaining = int(self.budgets[r, s] - self.g[r, s])
                useful_k = min(k_use, remaining - 1)
                acc = min(a, useful_k)
                self.drafted += useful_k
                self.accepted += acc
                self.rejected += useful_k - acc
                self.last_drafted[r, s] = useful_k
                self.last_accepted[r, s] = acc
                emit = int(min(a + 1, remaining))
                for j in range(emit):
                    # copy(): a bare dist[r, s, j] view would pin the
                    # whole [B, S, K+1, V] pass tensor in the history for
                    # the wave's lifetime — (K+1)x the plain path's score
                    # retention per pass.
                    self.hist_d[r][s].append(dist[r, s, j].copy())
                    self.hist_t[r][s].append(int(picks[r, s, j]))
                self.ctx[r][s] = np.concatenate(
                    [self.ctx[r][s], picks[r, s, :emit]]
                )
                self.g[r, s] = min(
                    self.g[r, s] + a + 1, self.budgets[r, s]
                )
                emitted[r, s] = emit
        self._fed = self._drafts = self._base = None
        return emitted

    def request_steps(self, row: int, s_off: int, s_cnt: int, n_steps: int):
        """Step-major history slices for ONE request's suffix span
        ([s_cnt, V] scores and [s_cnt] int64 token rows per step) — the
        serving engine's resolve/preemption-capture read path. Lives here
        so the ragged-history layout is indexed in exactly one module."""
        scores = [
            np.stack(
                [self.hist_d[row][s_off + s][t] for s in range(s_cnt)]
            )
            for t in range(n_steps)
        ]
        toks = [
            np.asarray(
                [self.hist_t[row][s_off + s][t] for s in range(s_cnt)],
                np.int64,
            )
            for t in range(n_steps)
        ]
        return scores, toks

    def step_major(self, n_steps: int):
        """Re-shape the ragged histories into the step-major
        ([B, S] per step) layout the offline output assembly expects —
        every row must have reached ``n_steps`` emissions."""
        bsz, s_b = self.g.shape
        dists = [
            np.stack(
                [
                    [self.hist_d[r][s][i] for s in range(s_b)]
                    for r in range(bsz)
                ]
            )
            for i in range(n_steps)
        ]
        toks = [
            np.array(
                [
                    [self.hist_t[r][s][i] for s in range(s_b)]
                    for r in range(bsz)
                ]
            )
            for i in range(n_steps)
        ]
        return dists, toks


# ---------------------------------------------------------------------------
# KV parking between shards / steps
# ---------------------------------------------------------------------------

def block_kv_bytes(model_cfg, dtype_name: str, toks, idxs, gen_slots: int):
    """Decode KV bytes for one block (all layers, compute dtype). Shared by
    the offline DecodeGenerator and the serving engine so the two KV
    placement decisions use ONE formula."""
    t0 = toks[idxs[0]]
    s_b, ls = t0.suffix_ids.shape
    lp = t0.prefix_ids.shape[-1]
    per_layer = (
        2  # k and v
        * len(idxs)
        * (lp + s_b * (ls + gen_slots))
        * model_cfg.num_key_value_heads
        * (model_cfg.head_dim + model_cfg.v_dim) / 2  # K/V dims differ (MLA)
    )
    bpe = np.dtype(np_dtype_for(dtype_name)).itemsize
    return per_layer * model_cfg.num_hidden_layers * bpe


def kv_fits_on_chip(
    model_cfg, dtype_name: str, toks, blocks, gen_slots: int,
    device=None, n_chips: int = 1,
) -> bool:
    """Whether every block's decode KV can stay in HBM alongside the
    resident weights (known-HBM chips only: weights + KV within 80% of the
    chip). A host-parked KV store costs a full KV round trip per shard per
    decode step over the host->HBM link, which can dwarf the decode math
    itself. Off on the CPU (``chip_hbm_gb`` is None there); a TPU whose
    capacity cannot be read raises — the gate never reads a failed probe as
    "does not fit"."""
    from flexible_llm_sharding_tpu.utils.metrics import (
        chip_hbm_gb,
        weight_bytes_per_chip,
    )

    hbm_gb = chip_hbm_gb(device)
    if not hbm_gb:
        return False
    kv_bytes = sum(
        block_kv_bytes(model_cfg, dtype_name, toks, i, gen_slots)
        for i in blocks
    )
    weights = weight_bytes_per_chip(model_cfg, dtype_name, n_chips)
    return weights + kv_bytes <= 0.8 * hbm_gb * 1e9


def extend_gen_kv(kv, gen_slots: int, dtype, device=None):
    """Pre-extend a prefill-parked KV pytree with ``gen_slots`` empty
    generated-token slots (``kg``/``vg``) so decode scans can donate in
    place. Head count/dims come from the prefill's own parked leaves, so
    MLA shapes (n_kv == n_heads; v_head_dim != qk head dim) allocate
    correctly without per-family math. Two distinct buffers: kg/vg are
    donated by the decode scan and must not alias. Allocated directly under
    ``device`` (the stage's chip / the tp mesh's replicated sharding):
    uncommitted zeros would all land on chip 0, concentrating every
    stage's gen-KV there during prefill. Shared by the offline prefill
    (DecodeGenerator) and the serving prefill (serve/engine.py)."""
    k_l, bsz, s_b = kv["ks"].shape[:3]

    def _gen_shape(like):
        return (k_l, bsz, s_b, gen_slots, like.shape[-2], like.shape[-1])

    return {
        **kv,
        "kg": jnp.zeros(_gen_shape(kv["ks"]), dtype, device=device),
        "vg": jnp.zeros(_gen_shape(kv["vs"]), dtype, device=device),
    }


class KVStore:
    """Per-(shard, block) KV pytrees. ``on_device`` keeps them in HBM —
    chosen for storage_location='tpu', and also for 'cpu'/'disk' when the
    weights are resident and the KV fits beside them (_kv_fits_on_chip);
    otherwise they park in host RAM (never on disk — the per-step access
    pattern would thrash it)."""

    def __init__(self, on_device: bool):
        self.on_device = on_device
        self._mem: dict[tuple, Any] = {}

    def put(self, key: tuple, kv) -> None:
        self._mem[key] = kv if self.on_device else jax.device_get(kv)

    def get(self, key: tuple, device=None):
        kv = self._mem.pop(key)
        if self.on_device:
            # MP pipeline: an activation parked by stage s lives on stage
            # s's chip; moving it to stage s+1's chip is a device-to-device
            # ICI hop (a no-op when it's already there).
            return kv if device is None else jax.device_put(kv, device)
        return jax.device_put(kv, device)

    def clear(self) -> None:
        self._mem.clear()


# ---------------------------------------------------------------------------
# The decode generator
# ---------------------------------------------------------------------------

class DecodeGenerator:
    """Streaming generation with KV reuse across tokens.

    ``__call__(prompts)`` -> (scores, updated_prompts) with the same output
    shapes as the slow loop: one float32 [n_suffixes, num_gen_token, vocab]
    per prompt and suffix strings grown by the decoded tokens.
    """

    def __init__(
        self,
        cfg: FrameworkConfig,
        device=None,
        tokenizer=None,
        weight_source_factory=None,
        mp_devices=None,
        resident: bool | None = None,
        draft_fn=None,
    ):
        # draft_fn(context_ids, k) -> exactly-k int64 draft ids: a custom
        # speculative draft source (HF assisted generation's pluggable
        # candidate-generator idea); defaults to prompt-lookup
        # (propose_draft). Verification is draft-agnostic — any source
        # keeps greedy-exact output; quality only changes acceptance.
        # weight_source_factory: DP mode passes views of one shared
        # BroadcastShardSource (rounds = num_gen_token — one per weight
        # stream, prefill plus each decode step — or 1 in resident mode) so
        # the checkpoint is read from disk once for all chips; see
        # orchestration.run_decode.
        # mp_devices: interleaved-pipeline decode — shard k's weights AND its
        # parked KV live on chip k % N (the reference's MP assignment,
        # /root/reference/utils.py:151-153); activations hop chip-to-chip
        # between stages. Mutually exclusive with weight_source_factory.
        if weight_source_factory is not None and mp_devices is not None:
            raise ValueError("mp_devices and weight_source_factory are exclusive")
        if weight_source_factory is not None and resident is None:
            # The caller built the shared source with a fixed round count;
            # an auto decision here could desync from it (consume one round
            # of many -> producer blocks; expect more rounds than built ->
            # consumer blocks). Make the coupling structural.
            raise ValueError(
                "weight_source_factory requires an explicit resident= flag "
                "matching the source's round count"
            )
        if weight_source_factory is not None and cfg.speculative_k:
            # The DP broadcast source's round count is fixed when it is
            # built; speculative passes are data-dependent (1..K+1 tokens
            # per pass), so the rank streams would desync from the producer.
            raise ValueError(
                "speculative_k does not compose with data_parallel decode"
            )
        self.weight_source_factory = weight_source_factory
        self._draft_fn = draft_fn if draft_fn is not None else propose_draft
        from flexible_llm_sharding_tpu.obs.registry import (
            REGISTRY,
            weak_source,
        )

        obs_trace.ensure_configured(cfg)
        REGISTRY.register("decode", weak_source(self))
        self.cfg = cfg
        self.model_cfg = LlamaConfig.from_pretrained(cfg.model_path)
        self.model_cfg.require_one_attention_shape("KV-cache decoding")
        self.model_cfg.require_single_visit("KV-cache decoding")
        self.device = device
        self.dtype = _DTYPES[cfg.dtype]
        if tokenizer is None:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(cfg.model_path)
        self.raw_tokenizer = tokenizer
        self.tokenizer = PromptTokenizer(
            tokenizer,
            max_token_len=cfg.max_token_len,
            bucket_multiple=cfg.bucket_multiple,
        )
        self.layer_names = checkpoint.layer_names_for(
            self.model_cfg.num_hidden_layers, tie_word_embeddings=False
        )
        if mp_devices is not None and len(mp_devices) > 1:
            from flexible_llm_sharding_tpu.parallel.planner import (
                global_stage_order,
            )

            stages = global_stage_order(
                len(self.layer_names), cfg.layer_num_per_shard, len(mp_devices)
            )
            self.shards = [s for (_, _, s) in stages]
            self.shard_devices = [mp_devices[r] for (_, r, _) in stages]
        else:
            if mp_devices:  # single chip: plain streaming decode
                device = self.device = mp_devices[0]
            self.shards = list(
                plan_shards_dp(len(self.layer_names), cfg.layer_num_per_shard).shards
            )
            self.shard_devices = [device] * len(self.shards)
        # Pallas kernels can't be auto-partitioned by GSPMD, so under
        # TpPlacement the flash calls run inside a shard_map over the heads
        # axis (llama._flash_tp_*); the placement's mesh rides into the
        # jitted blocks as a static arg (same design as StreamingExecutor).
        self._use_pallas = cfg.pallas_enabled()
        self._tp_mesh = (
            self.device.mesh if hasattr(self.device, "segment_target") else None
        )
        # Weights-resident decode: keep every placed shard on chip after
        # prefill and run decode steps with zero weight transfers (plain KV
        # decode re-streams the full model per step; the reference re-runs
        # the full PROMPT per step on top of that). Sized per chip: the tp
        # mesh splits each shard tp-ways, the MP pipeline spreads stages
        # round-robin. DP passes the decision in (``resident=``) so all
        # ranks agree with the shared broadcast source's round count.
        if self._tp_mesh is not None:
            self._n_chips = self._tp_mesh.devices.size
            self._probe_dev = next(iter(self._tp_mesh.devices.flat))
        else:
            distinct = {id(d) for d in self.shard_devices}
            self._n_chips = max(len(distinct), 1)
            self._probe_dev = self.shard_devices[0]
        if resident is not None:
            self._resident = resident
        else:
            self._resident = cfg.decode_resident_enabled(
                self.model_cfg, self._n_chips, self._probe_dev
            )
        # One placement target for the whole model (single chip, or one tp
        # mesh) — the precondition for fusing all decode steps into a single
        # XLA program (the MP pipeline's stages live on different chips and
        # keep the per-step loop).
        self._single_placement = (
            self._tp_mesh is not None
            or len({id(d) for d in self.shard_devices}) <= 1
        )
        # The one scheduling policy object (runtime/schedcore.py) — slot
        # sizing and KV residency decisions shared verbatim with the
        # serving engine so the two paths cannot drift.
        from flexible_llm_sharding_tpu.runtime.schedcore import SchedCore

        self._sched_core = SchedCore(cfg)
        self.stats: dict[str, float] = {}

    def _hbm_gb(self) -> float | None:
        from flexible_llm_sharding_tpu.utils.metrics import chip_hbm_gb

        # None on the CPU (auto gates resolve to off); raises on a TPU
        # whose capacity cannot be read.
        return chip_hbm_gb(self._probe_dev)

    def _weight_bytes(self) -> float:
        from flexible_llm_sharding_tpu.utils.metrics import (
            weight_bytes_per_chip,
        )

        return weight_bytes_per_chip(
            self.model_cfg, self.cfg.dtype, self._n_chips
        )

    def _block_kv_bytes(self, toks, idxs, gen_slots: int) -> int:
        """Decode KV bytes for one block (module fn block_kv_bytes)."""
        return block_kv_bytes(
            self.model_cfg, self.cfg.dtype, toks, idxs, gen_slots
        )

    def _kv_fits_on_chip(self, toks, blocks, gen_slots: int) -> bool:
        """Module fn kv_fits_on_chip at this generator's device/chip count
        (shared with the serving engine so the placement rule can't
        drift)."""
        return kv_fits_on_chip(
            self.model_cfg, self.cfg.dtype, toks, blocks, gen_slots,
            device=self._probe_dev, n_chips=self._n_chips,
        )

    def _fused_budget_ok(
        self, toks, blocks, n_gen: int, gen_slots: int, kv_on_device: bool
    ) -> bool:
        """Whether the fused scan's on-chip footprint fits: resident weights
        + KV (every block when the store is device-resident, else the
        largest single block staged per dispatch) + the scan's accumulated
        float32 dists stack [n_steps, B, S, V]. On the CPU backend "device
        memory" is host RAM — always ok; an accelerator with UNKNOWN HBM
        cannot be budgeted, so fusion stands down."""
        dev = self._probe_dev
        if dev is None:
            dev = jax.local_devices()[0]
        if getattr(dev, "platform", None) == "cpu":
            return True
        hbm_gb = self._hbm_gb()
        if not hbm_gb:
            return False
        per_block_kv = [
            self._block_kv_bytes(toks, i, gen_slots) for i in blocks
        ]
        kv_bytes = sum(per_block_kv) if kv_on_device else max(per_block_kv)
        dists_bytes = max(
            (n_gen - 1)
            * len(idxs)
            * toks[idxs[0]].suffix_ids.shape[0]
            * self.model_cfg.vocab_size
            * 4
            for idxs in blocks
        )
        total = self._weight_bytes() + kv_bytes + dists_bytes
        return total <= 0.8 * hbm_gb * 1e9

    def _open_streams(self, n_streams: int):
        """(per-pass stream factory, closer) for ``n_streams`` full weight
        passes — prefill + each decode step.

        DP mode (weight_source_factory): the SHARED BroadcastShardSource was
        built with rounds=num_gen_token, so its producer (and prefetch) runs
        continuously across passes; each call hands out the next round's
        view. Local mode: ONE ShardWeightSource over the shard list repeated
        n_streams times — per-pass sources would cold-start the prefetch
        pipeline at every decode step, leaving the chip idle for the first
        shard(s) of every token."""
        if self.weight_source_factory is not None:
            return (lambda: iter(self.weight_source_factory())), None
        from flexible_llm_sharding_tpu.faults.inject import FaultInjector
        from flexible_llm_sharding_tpu.runtime import hostcache, residency

        # Partial residency: moot in resident mode (every placed shard is
        # already kept on chip); in the streaming regime — the one the
        # tier exists for — every decode step's sweep skips the pinned
        # layers' link bytes.
        tier = (
            None
            if self._resident
            else residency.tier_for(
                self.cfg,
                self.layer_names,
                self.model_cfg.tie_word_embeddings,
                self._probe_dev,
            )
        )
        source = ShardWeightSource(
            self.cfg.model_path,
            self.layer_names,
            list(self.shards) * n_streams,
            np_dtype_for(self.cfg.dtype),
            devices=list(self.shard_devices) * n_streams,
            prefetch_depth=self.cfg.effective_prefetch_depth(),
            tied_embeddings=self.model_cfg.tie_word_embeddings,
            layer_sliding=self.model_cfg.layer_sliding,
            layer_rope=self.model_cfg.layer_rope,
            retry_policy=self.cfg.retry_policy(),
            injector=FaultInjector.from_config(self.cfg.faults),
            verify_weights=self.cfg.verify_weights,
            # Multi-sweep decode is the offline cache sweet spot: every
            # generated token past the first re-reads the same shards.
            host_cache=hostcache.cache_for(self.cfg),
            readahead_threads=self.cfg.readahead_threads,
            residency=tier,
        )
        it = iter(source)
        n_shards = len(self.shards)

        def one_pass():
            from itertools import islice

            return islice(it, n_shards)

        return one_pass, source

    def __call__(self, prompts, num_gen_token: int | None = None):
        cfg = self.cfg
        n_gen = num_gen_token or cfg.num_gen_token
        t_start = time.perf_counter()
        toks = [self.tokenizer(p, s) for p, s in prompts]
        # KV decode parks rope-rotated KV at prefill: fed positions must
        # not cross the longrope regime boundary (HF's dynamic table switch
        # would require re-rotating the parked cache). Plain decode feeds
        # tokens 1..n_gen-1; a speculative pass's fixed-width K+1 draft
        # window can overshoot by spec_k more.
        check_longrope_regime(
            self.model_cfg,
            toks,
            extra_len=max(n_gen - 1, 0)
            + (cfg.speculative_k if cfg.speculative_k else 0),
        )
        blocks = make_blocks(toks, cfg.block_size)
        # KV follows the weights: once the model is resident there is HBM
        # headroom, and host-parked KV would be re-uploaded per shard per
        # step — the dominant cost of a resident decode step. Both the slot
        # sizing and the residency call go through the shared SchedCore.
        plain_slots = self._sched_core.gen_slots(n_gen)
        kv_on_device = self._sched_core.kv_on_device(
            self.model_cfg, cfg.dtype, toks, blocks, plain_slots,
            self._resident, device=self._probe_dev, n_chips=self._n_chips,
        )
        kv_store = KVStore(on_device=kv_on_device)
        n_layers = len(self.layer_names)
        # Greedy + resident + one placement: run every decode step inside a
        # single jitted scan per block (_fused_decode_steps) instead of the
        # per-shard dispatch loop. Sampling keeps the loop (the numpy rng
        # stream is part of the documented determinism contract).
        budget_ok = bool(blocks) and self._fused_budget_ok(
            toks, blocks, n_gen, plain_slots, kv_on_device
        )
        fused = (
            cfg.decode_fused != "off"
            and self._resident
            and self._single_placement
            and cfg.temperature <= 0
            and n_gen > 1
            and budget_ok
        )
        if cfg.decode_fused == "on" and not fused and n_gen > 1 and blocks:
            raise ValueError(
                "decode_fused='on' needs resident weights, greedy selection, "
                "a single placement target (no MP pipeline), and the fused "
                "footprint (weights + KV + dists) within the chip's HBM; got "
                f"resident={self._resident} temperature={cfg.temperature} "
                f"single_placement={self._single_placement} "
                f"hbm_budget_ok={budget_ok}"
            )
        # Speculative verify passes (fused preferred when both could run:
        # resident steps move no weight bytes, so there is nothing for
        # speculation to amortise). Greedy-only, enforced by config.
        spec_k = cfg.speculative_k
        speculative = spec_k > 0 and n_gen > 1 and not fused and bool(blocks)
        # Generated-KV slots: plain decode fills one slot per step; a
        # speculative pass writes K+1 slots at per-suffix offsets capped at
        # n_gen-1, so the last write touches slot n_gen-1+K.
        gen_slots = self._sched_core.gen_slots(n_gen, spec_k, speculative)
        if speculative and kv_on_device and cfg.storage_location != "tpu":
            # Re-judge the resident-KV decision at the larger footprint.
            kv_on_device = self._sched_core.kv_on_device(
                self.model_cfg, cfg.dtype, toks, blocks, gen_slots,
                self._resident, device=self._probe_dev,
                n_chips=self._n_chips,
            )
            kv_store = KVStore(on_device=kv_on_device)

        block_meta = {
            b: (
                jnp.asarray(np.stack([toks[i].prefix_ids for i in idxs])),
                jnp.asarray(np.stack([toks[i].suffix_ids for i in idxs])),
                jnp.asarray(np.array([toks[i].prefix_len for i in idxs], np.int32)),
                jnp.asarray(np.stack([toks[i].suffix_eos for i in idxs])),
            )
            for b, idxs in enumerate(blocks)
        }
        # Per-block score accumulators [B, S, n_gen, V] and token histories.
        all_scores: dict[int, list[np.ndarray]] = {b: [] for b in range(len(blocks))}
        tok_hist: dict[int, list[np.ndarray]] = {b: [] for b in range(len(blocks))}

        # Token selection: greedy argmax (default), or temperature/top-k/
        # top-p sampling (deterministic per cfg.seed; padded suffix rows
        # never advance the rng). Scores stay the RAW distributions.
        from flexible_llm_sharding_tpu.runtime.generation import make_picker

        picker = make_picker(cfg)
        real_rows = {
            b: np.array(
                [
                    [si < toks[i].num_suffixes for si in range(toks[idxs[0]].suffix_ids.shape[0])]
                    for i in idxs
                ]
            )
            for b, idxs in enumerate(blocks)
        }
        pick = lambda dist, b: picker(dist, real=real_rows[b])  # noqa: E731

        one_pass, closer = self._open_streams(1 if self._resident else n_gen)
        # Resident mode: shards placed during prefill stay referenced here,
        # so every decode step walks them with zero host->HBM traffic.
        kept: list[tuple[int, tuple]] = []
        try:
            # --- prefill: one streaming pass, capturing KV ---------------
            for shard_pos, (layer_idxs, segments) in enumerate(one_pass()):
                if self._resident:
                    kept.append((shard_pos, (layer_idxs, segments)))
                if not layer_idxs:  # MP round-up padding stage
                    continue
                dev = self.shard_devices[shard_pos]
                # Activations/KV target: TpPlacement resolves to its
                # replicated sharding (weights alone carry the tp split).
                act_dev = getattr(dev, "act", dev)
                for b, idxs in enumerate(blocks):
                    prefix_ids, suffix_ids, prefix_len, suffix_eos = block_meta[b]
                    total_len = longrope_total_len(
                        self.model_cfg, prefix_len, suffix_eos
                    )
                    if layer_idxs[0] == 0:
                        ph, sh = None, None
                    else:
                        ph, sh = kv_store.get(("h", b), act_dev)
                    di = 0  # decoders-segment index within this shard: a
                    # shard can hold SEVERAL scan runs (llama4 interleaves
                    # dense and MoE layer structures), each with its own KV.
                    for kind, params in segments:
                        if kind == "embed":
                            ph, sh = _embed_block(
                                self.model_cfg, self.dtype, params, prefix_ids, suffix_ids
                            )
                        elif kind == "decoders":
                            ph, sh, kv = _prefill_decoders(
                                self.model_cfg, self._use_pallas,
                                self._tp_mesh, params, ph, sh, prefix_len,
                                total_len,
                            )
                            # gen_slots: one per decode step (min 1 so shapes
                            # stay non-degenerate at n_gen=1), widened for
                            # speculative passes' K+1-slot writes.
                            kv = extend_gen_kv(
                                kv, gen_slots, self.dtype, device=act_dev
                            )
                            kv_store.put(("kv", shard_pos, di, b), kv)
                            di += 1
                        elif kind == "norm":
                            sh = _norm_block(self.model_cfg, params, sh, suffix_eos)
                            ph = None
                        else:  # head
                            dist = np.asarray(jax.device_get(_head_block(self.model_cfg, params, sh)))
                            all_scores[b].append(dist)
                            tok_hist[b].append(pick(dist, b))
                    if layer_idxs[-1] != n_layers - 1:
                        kv_store.put(("h", b), (ph, sh))
                if closer is not None:
                    closer.dispatched()  # the next upload goes out behind these steps

            def stream_pass(embed_ids, decoders_fn, head_fn, skip_block=None):
                """One full-model walk (shards x blocks x segments) shared
                by the per-step loop and the speculative verify pass:
                kept-vs-streamed shard source, MP padding-stage skip,
                ('x', b) activation parking between shards, and the MP
                norm-hop (model.norm may live on an earlier stage's chip;
                its scale vector rides to the head's chip here).

                embed_ids(b) -> int token ids for block b;
                decoders_fn(b, params, kv, x, prefix_len, suffix_eos);
                head_fn(b, norm_params_on_chip, head_params, x);
                skip_block(b) -> True to leave a block out of this pass
                (speculative passes skip blocks whose rows all finished)."""
                norm_params = None
                for shard_pos, (layer_idxs, segments) in (
                    kept if self._resident else enumerate(one_pass())
                ):
                    if not layer_idxs:  # MP round-up padding stage
                        continue
                    dev = self.shard_devices[shard_pos]
                    act_dev = getattr(dev, "act", dev)
                    for b in range(len(blocks)):
                        if skip_block is not None and skip_block(b):
                            continue
                        _, _, prefix_len, suffix_eos = block_meta[b]
                        x = (
                            None
                            if layer_idxs[0] == 0
                            else kv_store.get(("x", b), act_dev)
                        )
                        di = 0
                        for kind, params in segments:
                            if kind == "embed":
                                x = llama.embed(
                                    params,
                                    jnp.asarray(embed_ids(b), jnp.int32),
                                    self.dtype,
                                    self.model_cfg,
                                )
                            elif kind == "decoders":
                                kv = kv_store.get(
                                    ("kv", shard_pos, di, b), act_dev
                                )
                                x, kv = decoders_fn(
                                    b, params, kv, x, prefix_len, suffix_eos
                                )
                                kv_store.put(("kv", shard_pos, di, b), kv)
                                di += 1
                            elif kind == "norm":
                                norm_params = params  # applied in the head
                            else:  # head
                                assert norm_params is not None
                                head_fn(
                                    b,
                                    jax.device_put(norm_params, act_dev),
                                    params,
                                    x,
                                )
                        if layer_idxs[-1] != n_layers - 1:
                            kv_store.put(("x", b), x)
                    if closer is not None:
                        closer.dispatched()

            # Traced wrapper: every full-model decode walk is one "sweep"
            # span (the offline counterpart of a serving sweep), so the
            # timeline shows per-token weight passes with their shard
            # loads/puts nested under the producer's stream spans.
            _stream_pass_untraced = stream_pass

            def stream_pass(embed_ids, decoders_fn, head_fn, skip_block=None):
                with obs_trace.sweep_span(
                    obs_trace.new_sweep_id(), cat="decode",
                    mode="decode_step",
                ):
                    return _stream_pass_untraced(
                        embed_ids, decoders_fn, head_fn, skip_block
                    )

            # --- decode steps ---------------------------------------------
            if fused:
                # Resident fused path: gather the kept segments once, then
                # one dispatch per block runs ALL steps on device.
                embed_p = norm_p = head_p = None
                dec_keys: list[tuple[int, int]] = []
                segs: list = []
                for shard_pos, (layer_idxs, segments) in kept:
                    di = 0
                    for kind, params in segments:
                        if kind == "embed":
                            embed_p = params
                        elif kind == "decoders":
                            dec_keys.append((shard_pos, di))
                            segs.append(params)
                            di += 1
                        elif kind == "norm":
                            norm_p = params
                        else:
                            head_p = params
                dev0 = self.shard_devices[0]
                act_dev = getattr(dev0, "act", dev0)
                for b, idxs in enumerate(blocks):
                    _, _, prefix_len, suffix_eos = block_meta[b]
                    kv_pairs = [
                        kv_store.get(("kv", sp, di, b), act_dev)
                        for sp, di in dec_keys
                    ]
                    kv_static = tuple(
                        {k: v for k, v in kv.items() if k not in ("kg", "vg")}
                        for kv in kv_pairs
                    )
                    kv_gen = tuple(
                        {"kg": kv["kg"], "vg": kv["vg"]} for kv in kv_pairs
                    )
                    del kv_pairs
                    dists, picks = _fused_decode_steps(
                        self.model_cfg,
                        self._use_pallas,
                        self._tp_mesh,
                        n_gen - 1,
                        self.dtype,
                        tuple(segs),
                        kv_static,
                        kv_gen,
                        embed_p,
                        norm_p,
                        head_p,
                        jnp.asarray(tok_hist[b][-1], jnp.int32),
                        prefix_len,
                        suffix_eos,
                    )
                    dists = np.asarray(jax.device_get(dists))
                    picks = np.asarray(jax.device_get(picks))
                    for s_i in range(n_gen - 1):
                        all_scores[b].append(dists[s_i])
                        tok_hist[b].append(picks[s_i])
            elif speculative:
                # --- speculative verify passes -----------------------------
                # Each pass streams the weights ONCE and verifies spec_k
                # prompt-lookup drafts plus the next token in a K+1-position
                # decode step, emitting 1..K+1 tokens per suffix — the
                # number of full weight streams per generated token drops by
                # the acceptance factor. Greedy-exact: position j's argmax
                # is precisely what sequential greedy would emit after the
                # accepted prefix, so outputs equal plain KV decode. The
                # accept/draft/slot-clock machinery lives in SpecVerifier
                # (one per block), shared verbatim with the serving engine.
                verifiers: dict[int, SpecVerifier] = {}
                for b, idxs in enumerate(blocks):
                    bsz = len(idxs)
                    s_b = toks[idxs[0]].suffix_ids.shape[0]
                    d0, t0 = all_scores[b][0], tok_hist[b][0]
                    verifiers[b] = SpecVerifier(
                        spec_k,
                        self._draft_fn,
                        draft_contexts([toks[i] for i in idxs], t0),
                        np.full((bsz, s_b), n_gen, np.int64),
                        d0,
                        t0,
                        active=[
                            [s < toks[i].num_suffixes for s in range(s_b)]
                            for i in idxs
                        ],
                    )
                while any(not v.done for v in verifiers.values()):
                    # Fed tokens/drafts are fixed per pass BEFORE streaming;
                    # blocks whose rows all finished sit the pass out
                    # (their state is frozen; recomputing them would only
                    # burn chip time and head transfers).
                    fed, base = {}, {}
                    for b, v in verifiers.items():
                        if not v.done:
                            fed[b], base[b] = v.begin_pass()
                    head_dists: dict[int, np.ndarray] = {}

                    def spec_head(b, norm_p, head_p, x):
                        head_dists[b] = np.asarray(
                            jax.device_get(
                                _spec_norm_head(
                                    self.model_cfg, norm_p, head_p, x
                                )
                            )
                        )

                    stream_pass(
                        lambda b: fed[b],
                        lambda b, params, kv, x, pl, se: _spec_decoders(
                            self.model_cfg, self._tp_mesh, params, kv, x,
                            pl, se, jnp.asarray(base[b]),
                        ),
                        spec_head,
                        skip_block=lambda b: b not in fed,
                    )
                    # Accept: longest draft prefix matching the argmax chain.
                    for b, dist in head_dists.items():
                        verifiers[b].finish_pass(dist)
                # Re-shape the ragged per-suffix histories into the common
                # step-major [B, S] layout the output assembly expects.
                for b, v in verifiers.items():
                    all_scores[b], tok_hist[b] = v.step_major(n_gen)
                spec_stats = {
                    "spec_passes": float(
                        max(v.passes for v in verifiers.values())
                    ),
                    "spec_drafted": float(
                        sum(v.drafted for v in verifiers.values())
                    ),
                    "spec_accepted": float(
                        sum(v.accepted for v in verifiers.values())
                    ),
                }
            # --- decode steps: stream weights, one token per suffix ------
            for t in ([] if fused or speculative else range(n_gen - 1)):

                def plain_head(b, norm_p, head_p, x):
                    dist = np.asarray(
                        jax.device_get(
                            _decode_norm_head(
                                self.model_cfg, norm_p, head_p, x
                            )
                        )
                    )
                    all_scores[b].append(dist)
                    tok_hist[b].append(pick(dist, b))

                stream_pass(
                    lambda b: tok_hist[b][-1][..., None],
                    lambda b, params, kv, x, pl, se: _decode_decoders(
                        self.model_cfg, self._use_pallas, self._tp_mesh,
                        params, kv, x, pl, se, jnp.int32(t),
                    ),
                    plain_head,
                )
        finally:
            if closer is not None:
                closer.close()

        kv_store.clear()
        kept.clear()  # release the resident weights
        self.stats = {
            "total_wall_s": time.perf_counter() - t_start,
            "decode_resident": float(self._resident),
            "decode_fused": float(fused),
            "decode_speculative": float(speculative),
            "decode_kv_on_device": float(kv_on_device),
            # Prefill runs every real prompt token once; each decode step
            # then runs exactly one new token per true suffix.
            "tokens_processed": float(
                sum(t.tokens_processed for t in toks)
                + sum(t.num_suffixes for t in toks) * max(n_gen - 1, 0)
            ),
        }
        if speculative:
            self.stats.update(spec_stats)

        # --- assemble outputs in prompt order ----------------------------
        scores_out: list[np.ndarray] = [None] * len(prompts)  # type: ignore
        updated: list = list(prompts)
        for b, idxs in enumerate(blocks):
            stacked = np.stack(all_scores[b], axis=2)  # [B, S, n_gen, V]
            hist = np.stack(tok_hist[b], axis=2)  # [B, S, n_gen]
            for row, i in enumerate(idxs):
                s_true = toks[i].num_suffixes
                scores_out[i] = stacked[row, :s_true]
                prefix, sfx = prompts[i]
                updated[i] = (
                    prefix,
                    tuple(
                        s + self.raw_tokenizer.decode(hist[row, s_i])
                        for s_i, s in enumerate(sfx)
                    ),
                )
        return scores_out, updated


__all__ = [
    "DecodeGenerator",
    "KVStore",
    "SpecVerifier",
    "block_kv_bytes",
    "draft_contexts",
    "extend_gen_kv",
    "kv_fits_on_chip",
    "propose_draft",
]
