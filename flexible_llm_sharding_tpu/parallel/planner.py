"""Shard planning: how the layer list is cut into shards and assigned to devices.

Reproduces the reference's planning math exactly
(``/root/reference/utils.py:144-153``):

- The "layer list" is the FULL execution list — ``model.embed_tokens``,
  ``model.layers.{i}``, ``model.norm``, ``lm_head`` — not just decoder layers.
- **DP** (each device streams the whole model over its own prompt slice):
  ``num_shards = ceil(n_layers / layer_num_per_shard)`` contiguous pieces via
  ``np.array_split`` (first ``n % num_shards`` pieces get one extra layer).
- **MP** (interleaved pipeline): shard count is rounded UP to a multiple of the
  device count, then device ``k`` takes shards ``all_shards[k::num_devices]``
  (round-robin / interleaved stages, cf. the reference's
  ``multigpu_flexibility.png``).

Prompt splitting for DP mode matches ``np.array_split(prompts, num_devices)``
(``/root/reference/main.py:70``).

A plan lists VISITS, not layers. For every model but a looped one they are
the same thing: each entry of the execution list once, in order. A looped
model (``LlamaConfig.total_ut_steps`` = T > 1) visits the embedding, then T
times the decoder layers and the final norm (which closes every step), then
the head: ``visit_order``. The shards are contiguous pieces of that order and
still hold indices into the execution list, so a layer's index appears T
times; what a shard's position means is ``shard_visit``'s to say, in one
place, for the executor, its recompute path and the pipeline runner alike.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import numpy as np


class ShardVisit(NamedTuple):
    """What a shard's place in the order of visits means for a block of
    prompts. The execution list is ``[embedding, decoder layers ..., final
    norm, head]`` (``n_layers`` entries: the norm is ``n_layers - 2``, the
    head ``n_layers - 1``), and the invariants are by VISIT:

    - the FIRST visit is the embedding's (index 0, visited once): the block
      has no activations yet, so nothing is fetched (``embeds``);
    - prefix states live until the LAST DECODER VISIT is done (the last
      step's visit of layer ``n_layers - 3``): a shard that starts after it
      holds only the final norm and the head, which read the suffixes' last
      tokens, and fetches no prefix (``needs_prefix`` false). In a looped
      model the final norm of an earlier step norms every prefix and suffix
      row for the next step, so the prefix lives through those step ends;
    - the LAST visit is the head's (index ``n_layers - 1``, visited once):
      nothing is stored after it and nothing is waited for at its shard's
      end (``stores`` false).

    ``step``: the loop step (0-based) of the shard's first layer; a shard
    may run over a step's end, and whoever walks its segments counts the
    norms it passes (``runtime/executor.apply_segments``)."""

    step: int
    embeds: bool
    needs_prefix: bool
    stores: bool


def shard_visit(
    layer_idxs, n_layers: int, step: int = 0, loop_steps: int = 1
) -> ShardVisit:
    """The role of one shard (see ``ShardVisit``). With ``loop_steps`` 1
    (every model but a looped one; the pipeline runner, which refuses
    those) the indices alone say it."""
    first, last = layer_idxs[0], layer_idxs[-1]
    return ShardVisit(
        step=step,
        embeds=first == 0,
        needs_prefix=step < loop_steps - 1 or first <= n_layers - 3,
        stores=last != n_layers - 1,
    )


def decoder_visits(layer_idxs, n_layers: int) -> int:
    """How many of a shard's visits are decoder layers' (neither the
    embedding's, the final norm's nor the head's)."""
    return sum(0 < i < n_layers - 2 for i in layer_idxs)


def visit_order(n_layers: int, loop_steps: int = 1) -> list[int]:
    """Indices into the execution list in the order a batch visits them:
    the embedding, ``loop_steps`` times (the decoder layers, the final
    norm), the head. ``range(n_layers)`` for ``loop_steps`` 1."""
    if loop_steps == 1:
        return list(range(n_layers))
    body = list(range(1, n_layers - 1))
    return [0] + body * loop_steps + [n_layers - 1]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """One device's work: the order of visits cut into shards, each a tuple
    of global layer indices (an index appears ``loop_steps`` times)."""

    shards: tuple[tuple[int, ...], ...]
    n_layers: int  # total layers in the model's execution list
    device_rank: int = 0
    num_devices: int = 1
    loop_steps: int = 1  # LlamaConfig.total_ut_steps

    @property
    def num_local_layers(self) -> int:
        return sum(len(s) for s in self.shards)

    def owns_layer(self, layer_idx: int) -> bool:
        return any(layer_idx in s for s in self.shards)

    def visits(self) -> list[ShardVisit]:
        """One ``ShardVisit`` per shard, in order: a shard's step is the
        number of final-norm visits before it."""
        out, step = [], 0
        for s in self.shards:
            out.append(shard_visit(s, self.n_layers, step, self.loop_steps))
            step += sum(i == self.n_layers - 2 for i in s)
        return out


def _array_split_sizes(n: int, parts: int) -> list[int]:
    """Sizes produced by ``np.array_split(np.arange(n), parts)``."""
    base, extra = divmod(n, parts)
    return [base + 1] * extra + [base] * (parts - extra)


def _contiguous_shards(
    n_layers: int, num_shards: int, order: list[int] | None = None
) -> list[tuple[int, ...]]:
    """``order`` (default ``range(n_layers)``) cut into ``num_shards``
    contiguous pieces of ``np.array_split`` sizes."""
    order = list(range(n_layers)) if order is None else order
    out, start = [], 0
    for size in _array_split_sizes(len(order), num_shards):
        out.append(tuple(order[start : start + size]))
        start += size
    return out


def plan_shards_dp(
    n_layers: int,
    layer_num_per_shard: int,
    device_rank: int = 0,
    num_devices: int = 1,
    loop_steps: int = 1,
) -> ShardPlan:
    """DP / single-device plan: contiguous shards, all streamed by this device
    (``/root/reference/utils.py:145-146``). ``device_rank``/``num_devices``
    identify the device within a DP group (used e.g. to tag per-rank disk
    activation files, ``/root/reference/utils.py:172``). ``loop_steps``
    (``LlamaConfig.total_ut_steps``): the shards cut ``visit_order``, the
    same rule over a longer list; at the default one layer a shard every
    step's shards are the same tuples, so its programs and its cache entries
    are the step before's."""
    order = visit_order(n_layers, loop_steps)
    num_shards = math.ceil(len(order) / layer_num_per_shard)
    return ShardPlan(
        shards=tuple(_contiguous_shards(n_layers, num_shards, order)),
        n_layers=n_layers,
        device_rank=device_rank,
        num_devices=num_devices,
        loop_steps=loop_steps,
    )


def _mp_num_shards(n_layers: int, layer_num_per_shard: int, num_devices: int) -> int:
    """MP shard count: rounded up to a multiple of ``num_devices`` so every
    device gets the same number of stages (``/root/reference/utils.py:151``)."""
    return (
        math.ceil(math.ceil(n_layers / layer_num_per_shard) / num_devices)
        * num_devices
    )


def plan_shards_mp(
    n_layers: int, layer_num_per_shard: int, device_rank: int, num_devices: int
) -> ShardPlan:
    """MP plan for one device: round-robin interleaved stages
    (``/root/reference/utils.py:150-153``)."""
    num_shards = _mp_num_shards(n_layers, layer_num_per_shard, num_devices)
    all_shards = _contiguous_shards(n_layers, num_shards)
    return ShardPlan(
        shards=tuple(all_shards[device_rank::num_devices]),
        n_layers=n_layers,
        device_rank=device_rank,
        num_devices=num_devices,
    )


def global_stage_order(n_layers: int, layer_num_per_shard: int, num_devices: int):
    """All MP stages in execution order as (stage_idx, device_rank, layer_tuple)."""
    num_shards = _mp_num_shards(n_layers, layer_num_per_shard, num_devices)
    shards = _contiguous_shards(n_layers, num_shards)
    return [(i, i % num_devices, s) for i, s in enumerate(shards)]


def split_prompts_dp(num_prompts: int, num_devices: int) -> list[tuple[int, int]]:
    """[start, end) prompt ranges per device — ``np.array_split`` semantics
    (``/root/reference/main.py:70``)."""
    sizes = _array_split_sizes(num_prompts, num_devices)
    ranges, start = [], 0
    for size in sizes:
        ranges.append((start, start + size))
        start += size
    return ranges


def batch_ranges(num_prompts: int, num_batch: int) -> list[tuple[int, int]]:
    """The reference's batching rule (``/root/reference/main.py:19-20``):
    ``num_batch`` pieces of size ``num_prompts // num_batch`` with the remainder
    folded into the last piece."""
    ends = [num_prompts // num_batch * i for i in range(1, num_batch)] + [num_prompts]
    return list(zip([0] + ends[:-1], ends))


__all__ = [
    "ShardPlan",
    "ShardVisit",
    "shard_visit",
    "decoder_visits",
    "visit_order",
    "plan_shards_dp",
    "plan_shards_mp",
    "global_stage_order",
    "split_prompts_dp",
    "batch_ranges",
]
