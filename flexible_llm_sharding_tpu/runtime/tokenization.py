"""Prompt tokenization for (prefix, suffixes) scoring prompts.

Token-level semantics match the reference exactly
(``/root/reference/utils.py:102-104,246-258``):

- ``pad_token = eos_token``, right padding;
- the prefix is tokenized unpadded (keeps its BOS), truncated to
  ``max_token_len``;
- suffixes are tokenized as a padded batch and the leading BOS column is
  stripped (``[:, 1:]``);
- ``suffix_eos[s]`` = index of the last non-pad token of suffix ``s``.

TPU-first addition: **length bucketing**. The reference feeds each prompt's
exact ragged shapes to CUDA kernels; under XLA every distinct shape is a new
compile, so here prefix/suffix lengths are right-padded up to a bucket multiple
and the number of suffixes up to a small multiple. True lengths travel
alongside as dynamic *values* (folded into attention masks / eos gathers), so
padding never changes numerics — only shapes.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def bucket_len(n: int, multiple: int, cap: int | None = None) -> int:
    """Round ``n`` up to a multiple (at least ``multiple``); clamp to ``cap``."""
    b = max(multiple, ((n + multiple - 1) // multiple) * multiple)
    return min(b, cap) if cap is not None else b


@dataclasses.dataclass
class TokenizedPrompt:
    """One (prefix, suffixes) prompt, padded to bucket shapes.

    prefix_ids: int32 [Lp_bucket]  (right-padded with pad_id)
    suffix_ids: int32 [S_bucket, Ls_bucket]  (padded rows are all pad_id)
    prefix_len: true prefix length (<= Lp_bucket)
    suffix_eos: int32 [S_bucket] — last real token index per suffix row
        (0 for padding rows; their scores are discarded)
    num_suffixes: true number of suffixes (<= S_bucket)
    """

    prefix_ids: np.ndarray
    suffix_ids: np.ndarray
    prefix_len: int
    suffix_eos: np.ndarray
    num_suffixes: int

    @property
    def bucket_key(self) -> tuple[int, int, int]:
        return (
            int(self.prefix_ids.shape[0]),
            int(self.suffix_ids.shape[0]),
            int(self.suffix_ids.shape[1]),
        )

    @property
    def tokens_processed(self) -> int:
        """Real (non-padding) tokens one full-model pass runs for this prompt:
        the prefix plus every true suffix's real tokens. The accounting unit
        of the CLI stats line's throughput (the benchmark's
        ``score_tokens_per_s`` counts the same tokens)."""
        return self.prefix_len + int(
            (self.suffix_eos[: self.num_suffixes] + 1).sum()
        )


class PromptTokenizer:
    """Wraps a HF tokenizer with the reference's prefix/suffix conventions."""

    def __init__(
        self,
        tokenizer,
        max_token_len: int = 4096,
        bucket_multiple: int = 64,
        suffix_count_multiple: int = 4,
    ):
        self.tok = tokenizer
        self.tok.pad_token = self.tok.eos_token
        self.tok.padding_side = "right"
        self.pad_id = self.tok.pad_token_id
        self.max_token_len = max_token_len
        self.bucket_multiple = bucket_multiple
        self.suffix_count_multiple = suffix_count_multiple

    def __call__(self, prefix: str, suffixes: tuple[str, ...]) -> TokenizedPrompt:
        prefix_ids = np.asarray(
            self.tok(
                prefix,
                return_attention_mask=False,
                truncation=True,
                max_length=self.max_token_len,
            )["input_ids"],
            dtype=np.int32,
        )
        # Padded suffix batch, leading BOS stripped (/root/reference/utils.py:252-257).
        suffix_ids = np.asarray(
            self.tok(
                list(suffixes),
                return_attention_mask=False,
                truncation=True,
                max_length=self.max_token_len,
                padding=True,
            )["input_ids"],
            dtype=np.int32,
        )[:, 1:]
        s, ls = suffix_ids.shape
        lp = prefix_ids.shape[0]

        lp_b = bucket_len(lp, self.bucket_multiple, self.max_token_len)
        ls_b = bucket_len(max(ls, 1), self.bucket_multiple, self.max_token_len)
        s_b = bucket_len(s, self.suffix_count_multiple)

        prefix_pad = np.full((lp_b,), self.pad_id, dtype=np.int32)
        prefix_pad[:lp] = prefix_ids  # lp_b >= lp by construction
        suffix_pad = np.full((s_b, ls_b), self.pad_id, dtype=np.int32)
        suffix_pad[:s, :ls] = suffix_ids

        # /root/reference/utils.py:258 — last non-pad index, zero-based.
        eos = np.zeros((s_b,), dtype=np.int32)
        eos[:s] = np.maximum((suffix_ids != self.pad_id).sum(axis=1) - 1, 0)

        return TokenizedPrompt(
            prefix_ids=prefix_pad,
            suffix_ids=suffix_pad,
            prefix_len=lp,
            suffix_eos=eos,
            num_suffixes=s,
        )


def extend_tokenized(
    tp: TokenizedPrompt,
    gen: np.ndarray,
    pad_id: int,
    bucket_multiple: int,
    max_token_len: int,
) -> TokenizedPrompt:
    """Fold already-generated token ids into a tokenized prompt's suffix
    rows — the preemption-resume path (serve/sched, docs/scheduling.md).

    ``gen`` is int32 ``[num_suffixes, n_done]``: the tokens each real
    suffix already received before its wave was preempted at a sweep
    boundary. They are appended as TOKEN IDS directly after each row's
    last real token (never a decode->retokenize round trip, which real
    tokenizers don't guarantee to invert), so the resumed prefill
    recomputes exactly the KV the interrupted decode held and the next
    greedy step continues token-identically. Raises ValueError when an
    extended row would exceed ``max_token_len`` (the wave-reject
    taxonomy turns that into a per-request failure, not an engine stop).
    """
    n_done = int(gen.shape[1])
    if n_done == 0:
        return tp
    eos = tp.suffix_eos
    longest = int(
        (eos[: tp.num_suffixes] + 1).max()
    ) + n_done if tp.num_suffixes else n_done
    if longest > max_token_len:
        raise ValueError(
            f"preemption resume would extend a suffix to {longest} tokens, "
            f"past max_token_len={max_token_len}"
        )
    s_b = tp.suffix_ids.shape[0]
    ls_new = bucket_len(longest, bucket_multiple, max_token_len)
    out = np.full((s_b, ls_new), pad_id, dtype=np.int32)
    new_eos = eos.copy()
    for s in range(tp.num_suffixes):
        real = int(eos[s]) + 1
        out[s, :real] = tp.suffix_ids[s, :real]
        out[s, real : real + n_done] = gen[s]
        new_eos[s] = real + n_done - 1
    return TokenizedPrompt(
        prefix_ids=tp.prefix_ids,
        suffix_ids=out,
        prefix_len=tp.prefix_len,
        suffix_eos=new_eos,
        num_suffixes=tp.num_suffixes,
    )


def longrope_total_len(model_cfg, prefix_len, suffix_eos):
    """Per-prompt real total length for longrope's long/short table choice
    (None for every other scaling kind). prefix_len: scalar or [B];
    suffix_eos: [S] or [B, S] — padded suffix rows carry eos 0, so the max
    over the last axis is the longest REAL suffix."""
    if model_cfg.rope_scaling_kind != "longrope":
        return None
    import jax.numpy as jnp

    return prefix_len + jnp.max(jnp.asarray(suffix_eos), axis=-1) + 1


def check_longrope_regime(model_cfg, toks, extra_len: int = 0, labels=None) -> None:
    """Loud precondition for longrope models (Phi-3 long-context).

    The long/short rope table is chosen per PROMPT by its real total
    length (ops/rope.py), while the streaming executor shares one prefix
    KV across all suffixes — so every (prefix + suffix) sequence of a
    prompt must sit on the same side of original_max_position_embeddings.
    ``extra_len`` is the maximum length growth the caller's decode steps
    can FEED beyond the initial sequence (KV decode: n_gen - 1, the last
    generated token is never fed back; speculative: plus spec_k for the
    widest draft window) — the grown length must not CROSS the boundary:
    KV parked under one regime cannot be re-rotated when HF's dynamic
    update would switch tables mid-generation.
    Raises ValueError naming the first offending prompt; ``labels`` maps
    positions in ``toks`` back to the caller's own prompt indices (for
    callers checking a filtered subset).
    """
    if model_cfg.rope_scaling_kind != "longrope":
        return
    orig = model_cfg.rope_original_max_position
    for i, t in enumerate(toks):
        lens = t.prefix_len + t.suffix_eos[: t.num_suffixes] + 1
        lo, hi = int(lens.min()), int(lens.max()) + extra_len
        if (lo <= orig) != (hi <= orig):
            label = labels[i] if labels is not None else i
            raise ValueError(
                f"prompt {label}: longrope sequence lengths {lo}..{hi} straddle "
                f"original_max_position_embeddings={orig}; the long/short "
                "rope regime must be uniform per prompt (split the prompt, "
                "shorten generation, or pad the prefix past the boundary)"
            )


def check_dense_len(model_cfg, toks, labels=None) -> None:
    """Loud precondition for a model whose softmax layers select blocks of
    keys from ``sparse_attn_from`` tokens on (learned block-sparse
    attention, which no path here computes): a prompt that long is refused,
    never truncated to fit nor run dense as if the rule were not there."""
    limit = model_cfg.sparse_attn_from
    if limit is None:
        return
    for i, t in enumerate(toks):
        longest = t.prefix_len + int(t.suffix_eos[: t.num_suffixes].max()) + 1
        if longest >= limit:
            label = labels[i] if labels is not None else i
            raise NotImplementedError(
                f"prompt {label}: {longest} tokens; {model_cfg.model_type} "
                f"switches its softmax layers to learned block-sparse "
                f"attention from {limit} tokens, which is not supported"
            )


def count_tokens(tokenizer, prompts, max_token_len: int = 4096) -> int:
    """Tokens one full scoring pass processes for ``prompts``, counted with
    the same semantics as PromptTokenizer (prefix truncated to
    ``max_token_len``; per-suffix leading BOS stripped). Host-side only —
    negligible next to a streaming pass; used by the CLI so its throughput
    line counts what ``tokens_processed`` counts without building the
    padded arrays."""
    total = 0
    for prefix, suffixes in prompts:
        pids = tokenizer(
            prefix, truncation=True, max_length=max_token_len
        )["input_ids"]
        total += len(pids)
        sids = tokenizer(
            list(suffixes), truncation=True, max_length=max_token_len
        )["input_ids"]
        total += sum(max(len(s) - 1, 0) for s in sids)
    return total


def make_blocks(
    tokenized: list[TokenizedPrompt], block_size: int
) -> list[list[int]]:
    """Group prompt indices into execution blocks of up to ``block_size``
    prompts sharing identical bucket shapes, preserving order within a bucket.

    A block is one jitted device call (vmapped over prompts) — the TPU
    replacement for the reference's strictly per-prompt loop
    (``/root/reference/utils.py:239``).
    """
    by_key: dict[tuple[int, int, int], list[int]] = {}
    for i, t in enumerate(tokenized):
        by_key.setdefault(t.bucket_key, []).append(i)
    blocks = []
    for key in sorted(by_key):
        idxs = by_key[key]
        for i in range(0, len(idxs), block_size):
            blocks.append(idxs[i : i + block_size])
    return blocks


__all__ = [
    "PromptTokenizer",
    "TokenizedPrompt",
    "extend_tokenized",
    "make_blocks",
    "bucket_len",
    "check_dense_len",
    "count_tokens",
]
