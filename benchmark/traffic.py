"""Traffic: a word-id tokenizer over the whole vocabulary and one general
prompt generator that reads a traffic file's parameters.

Repaired copies of ``bench.py``'s ``BenchTokenizer`` (ids covered 32,000 of
163,840 rows) and ``make_prompts`` (generator seeded with 0, one length).

Every seed gets the SAME multiset of lengths, in another order, with other
token ids: the lengths are the quantiles of the distribution the traffic file
names, so the work of a batch (and the set of compiled shapes) is the same
for every seed and only its arrangement and content change.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class WordIdTokenizer:
    """``"t17 t4093"`` -> ``[BOS, 17, 4093]``; no assets, no network. The
    surface the program's ``PromptTokenizer`` uses of a HF tokenizer."""

    BOS, EOS = 1, 2
    FIRST = 3  # ids below are special

    eos_token = "</s>"
    pad_token = "</s>"
    pad_token_id = EOS
    padding_side = "right"

    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)

    def _ids(self, text: str) -> list[int]:
        return [self.BOS] + [int(w[1:]) % self.vocab_size for w in text.split()]

    def decode(self, ids, **kw) -> str:
        if np.ndim(ids) == 0:
            ids = [int(ids)]
        return "".join(f" t{int(i)}" for i in ids)

    def __call__(self, text, max_length=None, padding=False, **kw):
        if isinstance(text, str):
            return {"input_ids": self._ids(text)[:max_length]}
        batch = [self._ids(t)[:max_length] for t in text]
        if padding:
            width = max(len(b) for b in batch)
            batch = [b + [self.pad_token_id] * (width - len(b)) for b in batch]
        return {"input_ids": batch}


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of ``spec``'s distribution
    (``uniform`` or ``log_uniform`` over [lo, hi], or a ``fixed`` list)."""
    if spec["dist"] == "fixed":
        vals = list(spec["values"])
        return [int(vals[i % len(vals)]) for i in range(n)]
    lo, hi = float(spec["lo"]), float(spec["hi"])
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "log_uniform":
        x = lo * (hi / lo) ** q
    elif spec["dist"] == "uniform":
        x = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(round(v)) for v in x]


def _text(rng, n_tokens: int, vocab: int) -> str:
    ids = rng.integers(WordIdTokenizer.FIRST, vocab, size=n_tokens)
    return " ".join(f"t{i}" for i in ids)


def make_batch(traffic: dict, vocab: int, seed: int, index: int) -> list:
    """Batch ``index`` of the run with ``seed``: ``prompts`` x ``suffixes``
    (prefix, (suffix, ...)) pairs. Lengths: the fixed multiset, permuted."""
    rng = np.random.default_rng([int(seed), int(index), 0x5C0])
    n, s = int(traffic["prompts"]), int(traffic["suffixes"])
    pre = rng.permutation(quantile_lengths(traffic["prefix_tokens"], n))
    suf = rng.permutation(quantile_lengths(traffic["suffix_tokens"], n * s))
    return [
        (
            _text(rng, int(pre[i]), vocab),
            tuple(_text(rng, int(suf[i * s + j]), vocab) for j in range(s)),
        )
        for i in range(n)
    ]


def count_tokens(tokenizer, prompts, max_token_len: int = 4096) -> int:
    """Real tokens one scoring pass processes (copy of the CLI's accounting:
    the prefix with its BOS, each suffix without its leading BOS)."""
    total = 0
    for prefix, suffixes in prompts:
        total += len(tokenizer(prefix, max_length=max_token_len)["input_ids"])
        sids = tokenizer(list(suffixes), max_length=max_token_len)["input_ids"]
        total += sum(max(len(s) - 1, 0) for s in sids)
    return total


def bucket(n: int, multiple: int = 64) -> int:
    return max(multiple, -(-n // multiple) * multiple)
