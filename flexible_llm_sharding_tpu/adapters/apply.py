"""Batched multi-adapter LoRA application inside one sweep.

One wave may mix rows from N tenants on different adapters plus the
base model. The base weights stream once; at each decoder layer ENTRY
the scan applies the grouped low-rank shift

    h += (h @ A_g) @ B_g * scale_g

where ``g`` maps each batch row to its adapter group (group 0 is always
the base, with zero factors and zero scale — base rows take the same
traced computation at zero delta). Implementation is gather-per-row:
``A``/``B`` are stacked ``[G, D, R]`` / ``[G, R, D]`` and each row
gathers its group's factors — at serving group counts (a handful of
adapters per wave) the gather is cheaper than segment-sorting the batch,
and it keeps row order stable so decode state never permutes.

Rank heterogeneity: every adapter pads with zeros to the wave max rank
R, which leaves the applied shift bit-identical (zero columns of A feed
zero rows of B).

This module is imported by the jitted decoder scans (runtime/decode.py)
— keep it dependency-light (jax + numpy only, no engine imports).
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import numpy as np


def lora_shift(h, a, b, g, scale):
    """The grouped delta at one decoder layer: ``h`` is batch-major
    ``[B, ..., D]`` hidden state, ``a``/``b`` are the stacked factors
    ``[G, D, R]``/``[G, R, D]``, ``g`` is the ``[B]`` int32 row->group
    map and ``scale`` the ``[G]`` float32 per-group multiplier. Returns
    ``h + ((h @ a[g]) @ b[g]) * scale[g]`` in ``h``'s dtype. Traced
    inside the decoder scans — pure jnp, no host work."""
    import jax.numpy as jnp

    ar = jnp.take(a, g, axis=0)  # [B, D, R]
    br = jnp.take(b, g, axis=0)  # [B, R, D]
    s = jnp.take(scale, g, axis=0)  # [B]
    u = jnp.einsum("b...d,bdr->b...r", h, ar)
    d = jnp.einsum("b...r,brd->b...d", u, br)
    s = s.reshape((h.shape[0],) + (1,) * (h.ndim - 1))
    return h + (d * s).astype(h.dtype)


def group_rows(adapter_ids: Sequence[str | None]) -> tuple[list, np.ndarray]:
    """Group a wave's per-row adapter ids: ``(names, g)`` where
    ``names[0]`` is always ``None`` (the base group, zero factors) and
    ``g[i]`` indexes ``names`` for row ``i``. First-seen order keeps the
    grouping deterministic for a given wave composition."""
    names: list = [None]
    index: dict = {None: 0}
    g = []
    for aid in adapter_ids:
        if aid not in index:
            index[aid] = len(names)
            names.append(aid)
        g.append(index[aid])
    return names, np.asarray(g, np.int32)


def group_scales(names: Sequence, plans: Mapping[str, Any]) -> np.ndarray:
    """[G] float32 apply scales, 0.0 for the base group."""
    return np.asarray(
        [0.0 if n is None else float(plans[n].scale) for n in names],
        np.float32,
    )


def stack_layer(
    names: Sequence,
    factors: Mapping[str, Mapping[str, Mapping[str, np.ndarray]]],
    layer_name: str,
    hidden: int,
    rank: int,
) -> tuple[np.ndarray, np.ndarray]:
    """One decoder layer's stacked factors ``(A [G, D, R], B [G, R, D])``
    (float32). The base group and adapters without a delta on this layer
    get zeros; smaller-rank adapters zero-pad to the wave rank ``R``
    (bit-identical — zero columns of A feed zero rows of B)."""
    a = np.zeros((len(names), hidden, rank), np.float32)
    b = np.zeros((len(names), rank, hidden), np.float32)
    for gi, name in enumerate(names):
        if name is None:
            continue
        pair = factors[name].get(layer_name)
        if pair is None:
            continue
        la, lb = pair["lora_A"], pair["lora_B"]
        r = int(la.shape[1])
        a[gi, :, :r] = la
        b[gi, :r, :] = lb
    return a, b


def delta_nbytes(delta: Mapping[str, Any] | None) -> int:
    """Host->HBM bytes one shard's delta arrays cost per sweep — the
    ``fls_adapter_delta_bytes`` charge, to be read against the base
    stream's bytes (a rank-sized sliver of them)."""
    if not delta:
        return 0
    return sum(
        int(v.nbytes) for v in delta.values() if hasattr(v, "nbytes")
    )


__all__ = [
    "delta_nbytes",
    "group_rows",
    "group_scales",
    "lora_shift",
    "stack_layer",
]
