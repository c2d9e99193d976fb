"""Grouped matmul: rows sorted by group, each group times its own matrix.

``lhs [M, K]`` holds the rows of group 0, then of group 1, ... (``group_sizes
[G]`` rows each; rows past their sum belong to no group), ``rhs [G, K, N]``
one matrix a group: ``out[i] = lhs[i] @ rhs[group of i]``. The expert layer's
routed body (``models/llama._routed_experts``) is three of these.

Two bodies. ``jax.lax.ragged_dot`` runs everywhere (on the CPU as a masked
dense expansion; on a TPU as XLA's own grouped-matmul custom call). The Pallas
kernel below is the TPU's fast path behind the caller's ``use_pallas``: on the
v5e, at the scoring cells' block sizes, XLA's call ran at 15-50% of the dense
einsum's rate per visited tile, a 128-row-tile kernel at 36-60% (PERF.md
section 6, PR 28). The kernel is ``jax.experimental.pallas.ops.tpu.megablox``'s
scheme (a dynamic grid over the (group, row tile) pairs that hold rows, each
visit storing only its group's rows of the tile) with the visit list computed
in a dozen array ops: megablox's own costs 0.15 s to trace and lower per call
shape, which every block shape of a warm-up sweep pays three times (PERF.md
section 6, PR 28). Rows past the groups are NOT written by either body:
callers must not trust them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128
# A weight tile's bytes: double-buffered beside the row tile, the output tile
# and the float32 accumulator it has to fit the kernel's 16 MiB of VMEM.
_WEIGHT_TILE_BYTES = 3 << 20


def supports(k: int, n: int, dtype) -> bool:
    """Kernel eligibility: lane-aligned contraction and output widths, 16-bit
    operands (float32 operands keep ``ragged_dot`` at full precision)."""
    return k % 128 == 0 and n % 128 == 0 and jnp.dtype(dtype).itemsize == 2


def _k_tile(k: int, n: int, itemsize: int) -> int:
    """The contraction's tile: cut in halves (lane multiples) until a
    ``[tk, n]`` weight tile fits its budget."""
    tk = k
    while tk * n * itemsize > _WEIGHT_TILE_BYTES and tk % 256 == 0:
        tk //= 2
    return tk


def _visits(group_sizes: jax.Array, m: int):
    """The (group, row tile) pairs that hold rows, in row order: ``offsets
    [G+1]`` (a group's first row; the end of the last), ``group [V]``, ``tile
    [V]`` over V = M/ROW_TILE + G - 1 slots, and how many of them are real.
    A tile that two groups share is visited once for each, back to back."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // ROW_TILE
    tiles = jnp.where(group_sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    v = jnp.arange(m // ROW_TILE + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), g - 1)
    tile = first[group] + v - (visit_ends - tiles)[group]
    tile = jnp.clip(tile, 0, m // ROW_TILE - 1)  # slots past the real ones
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (
        offsets.astype(jnp.int32), group.astype(jnp.int32), tile.astype(jnp.int32),
        visit_ends[-1].astype(jnp.int32),
    )


def _kernel(offsets, group, tile, lhs, rhs, out, acc, *, k_tiles: int):
    visit, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...], preferred_element_type=jnp.float32)

    @pl.when(ki == k_tiles - 1)
    def _():
        # Only this group's rows of the tile: the others are a neighbour's,
        # stored by its own visit while the tile stays in VMEM, or nobody's.
        g = group[visit]
        row = tile[visit] * ROW_TILE + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out[...] = jnp.where(mine, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)


def _pallas_grouped_matmul(lhs, rhs, visits, out_dtype, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[-1]
    tk = _k_tile(k, n, lhs.dtype.itemsize)
    offsets, group, tile, real = visits
    return pl.pallas_call(
        functools.partial(_kernel, k_tiles=k // tk),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((ROW_TILE, tk), lambda v, ki, o, g, t: (t[v], ki)),
                pl.BlockSpec((None, tk, n), lambda v, ki, o, g, t: (g[v], ki, 0)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, n), lambda v, ki, o, g, t: (t[v], 0)),
            grid=(real, k // tk),
            scratch_shapes=[pltpu.VMEM((ROW_TILE, n), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, group, tile, lhs, rhs)


def for_groups(group_sizes: jax.Array, use_pallas: bool = False, precision=None):
    """The grouped matmul of one grouping (``group_sizes [G]``, int32), for
    several operands: ``f(lhs [M, K], rhs [G, K, N], out_dtype=None) -> [M,
    N]`` (default: ``lhs``'s dtype), accumulated in float32. With
    ``use_pallas`` a call takes the kernel where ``M`` is a multiple of
    ``ROW_TILE`` and the widths are eligible (``supports``), and the calls
    share one visit list; ``precision`` goes to ``ragged_dot``."""
    visits = {}  # by M: the kernel's visit list, computed at its first use

    def f(lhs, rhs, out_dtype=None):
        (m, k), n = lhs.shape, rhs.shape[-1]
        out_dtype = out_dtype or lhs.dtype
        if use_pallas and m % ROW_TILE == 0 and supports(k, n, lhs.dtype):
            if m not in visits:
                visits[m] = _visits(group_sizes, m)
            return _pallas_grouped_matmul(
                lhs, rhs, visits[m], out_dtype,
                interpret=jax.default_backend() != "tpu",  # as ops/pallas_attention.py
            )
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, precision=precision, preferred_element_type=out_dtype
        )

    return f
