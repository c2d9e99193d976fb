"""Pallas TPU flash-attention kernels for the streaming scorer's hot ops.

The XLA path (ops/attention.py) materialises the [Lq, Lk] score matrix in
fp32; at the reference's 4096-token cap that is 64 MB per head — far over
VMEM — so XLA spills it to HBM and the op becomes bandwidth-bound. These
kernels stream KV through VMEM in blocks with an online softmax (flash
attention), so scores never leave VMEM and the op stays MXU-bound.

Two kernels, sharing one inner block routine:

- :func:`flash_causal_attention` — causal self-attention with a dynamic
  valid-length (the prefix pass of ``llama.prefix_suffix_layer``;
  reference semantics ``/root/reference/utils.py:270-274``).
- :func:`flash_prefix_shared_attention` — S suffix continuations attending
  to [shared prefix KV ; own causal KV] with a joint softmax, the kernel
  form of ``ops.attention.prefix_shared_attention`` (the reference's KV
  ``.expand`` trick, ``/root/reference/utils.py:272-279``). The prefix KV
  block is read per (suffix, head, q-block) program straight from HBM-fed
  VMEM blocks — never copied S times into a concatenated buffer.

Both operate on one head per program (grid dims pick the head and q block);
GQA is handled by the KV index map (query head h reads KV head
``h * n_kv // n_q``), so KV heads are never replicated. Inputs keep the
model dtype (bf16 on the MXU); softmax runs in fp32 VMEM accumulators.

How blocks are chosen (:func:`flash_tiles`): by what the chip does fastest,
not by what divides the bucket. A step of the online softmax has a fixed
cost that a 64 x 64 block (all that divides a bucket of 1216 or 3392)
never repays, so the two scoring kernels take 256 query rows x 512 keys
under the diagonal, a suffix's 64 rows over up to 4096 prefix keys, and
tiles near a binding window, a pure table of the call's static shapes; the
wrappers zero-pad the length axes up to a whole number of tiles and slice
the output back. That is exact: padded keys lie past ``valid_len``, where
the loop bounds and the mask already stop, and padded query rows are cut
off. K and V of a KV head stay whole in VMEM (the largest bucket, 4096 x
256 bf16, K and V double-buffered: 8 MB beside a 0.5-1 MB score tile).
:func:`causal_steps` / :func:`prefix_shared_steps` count the steps a call
runs from the same tiles and bounds (the sweep record's ``flash_steps``).
The decode kernel keeps divisor blocks (``_block``): no cell runs it yet.

Model-family envelope (mirrors the XLA ops' full surface):

- ``scale`` — custom attention scale (Gemma2's query_pre_attn_scalar).
- ``softcap`` — Gemma2/3 attention-logit softcapping, applied to the scaled
  fp32 scores before the mask (HF eager order: scale -> softcap -> mask).
- ``window`` / ``chunk`` — Mistral/Qwen sliding window or Llama4 chunked
  attention (static ints); KV blocks wholly outside the local region are
  SKIPPED, not just masked, so a binding window also cuts FLOPs/bandwidth.
- ``local_on`` — the per-layer local-attention toggle (Gemma2/3, Llama4
  alternation under one ``lax.scan`` program): a traced bool that rides the
  scalar-prefetch channel next to ``prefix_len``.
- ``sink`` — an optional learned logit per query head (MiMo-V2's window
  layers) that joins the softmax's denominator and carries no value: it is
  where the online softmax STARTS (running max = sink, denominator = 1,
  accumulator = 0), so no block sees it again. It rides a second
  scalar-prefetch operand (float32 [n_q]); ``None`` leaves the kernels as
  they are without one.

Shape eligibility is checked by :func:`supports` / :func:`supports_decode`;
callers fall back to the XLA path otherwise. Ragged head dims >= 64 (phi3's
96) are zero-padded to the lane multiple inside the scoring wrappers (exact;
at most 2x lanes); tiny head dims, unbucketed lengths (the envelope callers
are held to, though the wrappers' padding would carry any length), and — for
the decode kernel — any non-128-multiple head dim fall back to XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

_MAX_BLOCK_K = 512  # decode kernel: keys streamed through VMEM per flash step

# The scoring kernels' tiles (flash_tiles), read off a sweep on a v5e (PERF.md
# section 6, PR 34). A step of the online softmax costs ~0.35 us there
# whatever is in it (a dynamic-trip loop with a carried (m, l, acc), two MXU
# round trips, the cross-lane max and sum, the mask), so a step should carry
# as large a score tile as pays. Under the diagonal that is 256 x 512 (the
# fastest of 128-512 x 128-1024 for every head shape tried: wider key tiles
# waste more of the diagonal block, taller ones spill). The prefix walk of a
# suffix's 64 rows has no diagonal and kept gaining up to the whole prefix in
# a step (64 x 4096).
_TILE_Q = 256  # query rows per program
_TILE_K = 512  # keys per flash step under a query tile of more than 128 rows
_SMALL_Q_SCORES = 64 * 4096  # score-tile elements under a smaller query tile


def _block(n: int, cap: int) -> int:
    """Largest power-of-two-ish tile <= cap that divides n (n % 64 == 0
    callers guaranteed by supports(); fall back to n itself). The decode
    kernel's prefix walk only: the scoring kernels take ``flash_tiles``."""
    for b in (cap, 256, 128, 64):
        if b <= cap and n % b == 0:
            return b
    return n


def _cdiv(a, b):
    return -(-a // b)


def _fit(n: int, cap: int, grain: int) -> int:
    """The tile of a length-``n`` axis under ``cap``: as few tiles as the
    cap allows, evenly sized, rounded up to ``grain`` (the axis is then
    zero-padded to a whole number of tiles). A length of at most 64 is its
    own tile (a block equal to the array's dim needs no alignment)."""
    if n <= 64:
        return n
    tiles = _cdiv(n, cap)
    return _cdiv(_cdiv(n, tiles), grain) * grain


def flash_tiles(
    lq: int, lk: int, hd: int, dv: int, window: int | None = None,
    chunk: int | None = None,
) -> tuple[int, int]:
    """(query rows per program, keys per flash step) of the two scoring
    kernels, from a call's static shapes alone: ``flash_causal_attention``
    over ``lq`` queries and ``lk`` keys, and the prefix walk of
    ``flash_prefix_shared_attention`` (``lq`` = one suffix's rows, ``lk`` =
    the prefix bucket). ``hd`` / ``dv``: the q/k and the v head dims; the
    sweep found ONE table fastest at 128 / 128 and at 192 / 128 (padded to
    256), so nothing branches on them yet.

    Full attention takes the tiles the sweep found fastest. A binding
    ``window`` / ``chunk`` takes a query tile near it and a key tile of
    twice that (a window-sized query tile sees under two windows of keys:
    one key tile half the time, two the other half; a 512-key tile over a
    window of 128 computes four times the scores the window needs). The
    lengths need not divide: the wrappers zero-pad them up to the tile."""
    local = window if window is not None else chunk
    if local is not None and 2 * local <= _TILE_K:
        near = max(128, 1 << (local - 1).bit_length())
        return _fit(lq, near, 64), _fit(lk, 2 * near, 128)
    bq = _fit(lq, _TILE_Q, 64)
    return bq, _fit(lk, _TILE_K if bq > 128 else _SMALL_Q_SCORES // bq, 128)


def supports(
    n_q: int, n_kv: int, head_dim: int, lq: int, lk: int, v_dim: int | None = None
) -> bool:
    """Kernel eligibility: whole query groups and bucketed q/k lengths.
    Ragged head dims >= 64 (phi3's 96) are zero-padded to the lane multiple
    inside the wrappers — exact, since zero channels contribute nothing to
    QK^T and the padded V channels are sliced off, and the pad costs at most
    2x lanes. Tinier head dims fall back to XLA (an 8x pad would waste more
    MXU/bandwidth than the kernel saves). ``v_dim``: V's own head dim where
    it differs from q/k's (MLA: qk 192 vs v 128) — the scoring kernels carry
    the two dims independently (QK^T over head_dim, PV over v_dim)."""
    if v_dim is None:
        v_dim = head_dim
    return (
        n_q % n_kv == 0
        and lq % 64 == 0
        and lk % 64 == 0
        and head_dim >= 64
        and v_dim >= 64
    )


def _pad_dim(a, axis: int, mult: int):
    """Zero-pad ``axis`` of ``a`` up to a multiple of ``mult``."""
    p = (-a.shape[axis]) % mult
    if not p:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, p)
    return jnp.pad(a, pad)


def _pad_head_dim(*arrays):
    """Zero-pad the trailing head_dim axis of each array to a multiple of
    128 (the TPU lane width). Returns (padded_arrays, original_hd)."""
    hd = arrays[0].shape[-1]
    return tuple(_pad_dim(a, -1, 128) for a in arrays), hd


def _online_block(q, kb, vb, mask, m, l, acc, scale, softcap=None):
    """One flash step: fold a KV block into the (m, l, acc) accumulators.

    q [Bq, hd] model dtype; kb/vb [Bk, hd]; mask [Bq, Bk] bool;
    m/l [Bq, 1] fp32; acc [Bq, hd] fp32.
    """
    s = jax.lax.dot_general(
        q,
        kb,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jax.lax.dot_general(
        p.astype(vb.dtype),
        vb,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def _start(rows: int, dv: int, sink=None):
    """The online softmax's initial (m, l, acc) for ``rows`` query rows.
    ``sink``: None, or the rows' sink logits (a scalar or [rows, 1] fp32):
    exp(sink - m) = 1 is already in the denominator."""
    acc = jnp.zeros((rows, dv), jnp.float32)
    if sink is None:
        m = jnp.full((rows, 1), _NEG_INF, jnp.float32)
        return m, jnp.zeros((rows, 1), jnp.float32), acc
    m = jnp.broadcast_to(jnp.asarray(sink, jnp.float32), (rows, 1))
    return m, jnp.ones((rows, 1), jnp.float32), acc


def _finish(l, acc, dtype):
    """acc / l with fully-masked rows (padding queries) zeroed."""
    return jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0).astype(dtype)


def _local_mask(mask, q_pos, k_pos, window, chunk, local_on):
    """AND the local-attention clause into ``mask`` (ops.attention
    ``_local_clause`` semantics): visible iff within the sliding ``window``
    (q - k < window) or sharing a position ``chunk``; a False ``local_on``
    (the traced per-layer toggle) disables the clause."""
    if window is not None:
        in_local = (q_pos - k_pos) < window
    elif chunk is not None:
        in_local = (q_pos // chunk) == (k_pos // chunk)
    else:
        return mask
    return mask & (jnp.logical_not(local_on) | in_local)


def _kv_bounds(xp, q0, bq, bk, nk, plen, causal, window, chunk, local_on):
    """The KV blocks ``[first, last)`` (of ``bk`` keys, ``nk`` of them, the
    first ``plen`` keys real) that a q block of ``bq`` rows walks, its first
    row at absolute position ``q0``: all that can hold a visible key. None
    past the valid length (the wrappers' zero padding lies there), none
    wholly above the diagonal (``causal``: the keys' positions are the rows'
    own axis), none wholly before the earliest key a binding window/chunk
    lets the FIRST row see (later rows only look further right).

    One source for the three kernels' loop bounds (``xp`` = jnp, traced
    scalars; the decode kernel's one query is a block of one row) and the
    host's count of the scoring kernels' steps (``xp`` = numpy, ``q0`` an
    array over q blocks): ``causal_steps`` / ``prefix_shared_steps``."""
    last = xp.minimum(_cdiv(plen, bk), nk)
    if causal:
        last = xp.minimum(last, _cdiv(q0 + bq, bk))
    if window is not None:
        first = xp.maximum(q0 - window + 1, 0) // bk
    elif chunk is not None:
        first = (q0 // chunk) * chunk // bk
    else:
        return 0 * last, last
    return xp.minimum(xp.where(local_on, first, 0), last), last


def causal_steps(
    lq: int, lk: int, hd: int, dv: int, valid_len: int,
    window: int | None = None, chunk: int | None = None, local_on: bool = True,
) -> int:
    """Steps of the online softmax that ONE query head of a
    ``flash_causal_attention`` call runs: the trips of its programs' loops,
    by the wrapper's own tiles and the kernel's own bounds. A host count
    (numpy over the q blocks): no step is run to count it."""
    bq, bk = flash_tiles(lq, lk, hd, dv, window, chunk)
    q0 = np.arange(_cdiv(lq, bq)) * bq
    first, last = _kv_bounds(
        np, q0, bq, bk, _cdiv(lk, bk), valid_len, True, window, chunk, local_on
    )
    return int((last - first).sum())


def prefix_shared_steps(
    ls: int, lp: int, hd: int, dv: int, prefix_len: int,
    window: int | None = None, chunk: int | None = None, local_on: bool = True,
) -> int:
    """As ``causal_steps``, for one query head and ONE suffix of a
    ``flash_prefix_shared_attention`` call: the prefix walk of each of its q
    blocks and the one step over the suffix's own keys."""
    bq, bkp = flash_tiles(ls, lp, hd, dv, window, chunk)
    q0 = prefix_len + np.arange(_cdiv(ls, bq)) * bq
    first, last = _kv_bounds(
        np, q0, bq, bkp, _cdiv(lp, bkp), prefix_len, False, window, chunk,
        local_on,
    )
    return int((last - first + 1).sum())


# ---------------------------------------------------------------------------
# Causal self-attention with dynamic valid length (prefix pass)
# ---------------------------------------------------------------------------

def _causal_kernel(
    flags_ref, *refs, scale, lk, bk, window, chunk, softcap, has_sink,
):
    sink_ref, (q_ref, k_ref, v_ref, o_ref) = _split_sink(refs, has_sink)
    # Head-major blocks: q_ref [1, bq, hd]; k_ref [1, lk, hd]; v_ref
    # [1, lk, dv] (dv == hd except MLA, where V has its own head dim). The
    # TPU lowering constrains only the last two block dims, so the head axis
    # must lead with block size 1.
    qb = pl.program_id(1)
    _, bq, _ = q_ref.shape
    dv = v_ref.shape[-1]
    q = q_ref[0]
    plen = flags_ref[0]
    local_on = flags_ref[1] != 0
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    m, l, acc = _start(
        bq, dv, None if sink_ref is None else sink_ref[pl.program_id(0)]
    )

    def body(blk, carry):
        start = pl.multiple_of(blk * bk, bk)
        kb = k_ref[0, pl.ds(start, bk), :]
        vb = v_ref[0, pl.ds(start, bk), :]
        kj = start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = _local_mask(
            (kj <= qi) & (kj < plen), qi, kj, window, chunk, local_on
        )
        return _online_block(q, kb, vb, mask, *carry, scale, softcap)

    # Causal: KV blocks wholly above this q block's diagonal contribute
    # nothing, and neither do blocks past the valid length (every key there
    # has kj >= plen, the wrapper's zero padding among them) — stop at
    # whichever bound comes first. A binding local form also skips blocks
    # wholly before the window/chunk.
    first, last = _kv_bounds(
        jnp, qb * bq, bq, bk, lk // bk, plen, True, window, chunk, local_on
    )
    m, l, acc = jax.lax.fori_loop(first, last, body, (m, l, acc))
    o_ref[0] = _finish(l, acc, o_ref.dtype)


def _split_sink(refs, has_sink: bool):
    """(sink_ref or None, the other refs): the sink, when there is one, is
    the second scalar-prefetch operand and so the first of ``refs``."""
    return (refs[0], refs[1:]) if has_sink else (None, refs)


def _prefetch(flags, sink) -> tuple:
    """The scalar-prefetch operands: the int32 flags, then the float32
    per-head sink logits when the layer has them."""
    if sink is None:
        return (flags,)
    return (flags, jnp.asarray(sink, jnp.float32))


def _flags(prefix_len, local_on) -> jax.Array:
    """Scalar-prefetch payload: [prefix_len, local_on] int32. ``local_on``
    None means the static local form (if any) applies unconditionally."""
    flag = jnp.asarray(True if local_on is None else local_on)
    return jnp.stack(
        [jnp.asarray(prefix_len, jnp.int32), flag.astype(jnp.int32)]
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "chunk", "softcap", "interpret"),
)
def flash_causal_attention(
    q, k, v, valid_len, scale=None, window=None, chunk=None, softcap=None,
    local_on=None, interpret=None, sink=None,
):
    """q [L, n_q, hd], k [L, n_kv, hd], v [L, n_kv, dv], valid_len int32
    scalar -> [L, n_q, dv] (dv == hd everywhere but MLA, whose V has its
    own head dim). Query i attends keys j with j <= i and j < valid_len,
    optionally restricted to a sliding ``window`` / position ``chunk``
    (``local_on``: traced per-layer toggle, None = on). ``sink`` [n_q]: a
    logit per head in the softmax's denominator."""
    if interpret is None:
        # Auto: compiled on real TPU, interpreter elsewhere (lets the CPU
        # test mesh exercise the kernels end-to-end, incl. under shard_map).
        interpret = jax.default_backend() != "tpu"
    lq, n_q, hd = q.shape
    lk, n_kv, _ = k.shape
    if scale is None:
        scale = 1.0 / (hd**0.5)
    # q/k pad together (QK^T dim); v pads on its OWN dim (MLA: 192 vs 128).
    (q, k), _ = _pad_head_dim(q, k)
    (v,), dv_true = _pad_head_dim(v)
    hd, dv = q.shape[-1], v.shape[-1]
    # Head-major, and the length axes zero-padded up to the tiles: padded
    # keys sit at kj >= lk >= valid_len, which the loop bounds and the mask
    # exclude (their V rows are zeros, not garbage: a masked p = 0 times a
    # NaN would be a NaN in the PV matmul); padded query rows are sliced off.
    bq, bk = flash_tiles(lq, lk, hd, dv, window, chunk)
    q = _pad_dim(q.transpose(1, 0, 2), 1, bq)
    k = _pad_dim(k.transpose(1, 0, 2), 1, bk)
    v = _pad_dim(v.transpose(1, 0, 2), 1, bk)
    lqp, lkp = q.shape[1], k.shape[1]
    grid = (n_q, lqp // bq)
    kv_head = lambda h, qb, *_: (h * n_kv // n_q, 0, 0)
    prefetch = _prefetch(_flags(valid_len, local_on), sink)

    kernel = functools.partial(
        _causal_kernel, scale=scale, lk=lkp, bk=bk, window=window, chunk=chunk,
        softcap=softcap, has_sink=sink is not None,
    )
    # Named three times over. The TPU names the HLO instruction after the
    # innermost scope of its ``op_name``, and under vmap the call runs in
    # Pallas's own batching loop (the flags are a batched scalar-prefetch
    # operand), whose body is a ``closed_call`` no scope of ours can get
    # inside. So: ``name`` for the kernel's own name, the scope for the
    # ``op_name``, and ``metadata`` for the instruction's
    # ``frontend_attributes``, which is in the HLO line a profiler trace
    # shows for the op whatever the instruction is called.
    with jax.named_scope("flash_causal_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, bq, hd), lambda h, qb, *_: (h, qb, 0)),
                    pl.BlockSpec((1, lkp, hd), kv_head),
                    pl.BlockSpec((1, lkp, dv), kv_head),
                ],
                out_specs=pl.BlockSpec((1, bq, dv), lambda h, qb, *_: (h, qb, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((n_q, lqp, dv), q.dtype),
            interpret=interpret,
            name="flash_causal_attention",
            metadata={"kernel": "flash_causal_attention"},
        )(*prefetch, q, k, v)
    return out[:, :lq, :dv_true].transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Prefix-shared suffix attention (joint softmax over [prefix ; own causal])
# ---------------------------------------------------------------------------

def _prefix_shared_kernel(
    flags_ref, *refs, scale, lp, bkp, window, chunk, softcap, has_sink,
):
    sink_ref, (q_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref) = _split_sink(
        refs, has_sink
    )
    # Head-major blocks: q_ref [1, 1, bq, hd]; kp_ref [1, lp, hd]; vp_ref
    # [1, lp, dv]; ks_ref [1, 1, ls, hd]; vs_ref [1, 1, ls, dv] (dv == hd
    # except MLA, where V has its own head dim).
    qb = pl.program_id(2)
    _, _, bq, _ = q_ref.shape
    dv = vp_ref.shape[-1]
    q = q_ref[0, 0]
    plen = flags_ref[0]
    local_on = flags_ref[1] != 0
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    # Absolute positions: suffix query i sits at prefix_len + i; prefix key
    # j at j; suffix key j at prefix_len + j (ops.attention convention).
    q_abs = plen + qi

    m, l, acc = _start(
        bq, dv, None if sink_ref is None else sink_ref[pl.program_id(1)]
    )

    # Prefix KV: visible iff the key is real (j < plen); no causality.
    def p_body(blk, carry):
        start = pl.multiple_of(blk * bkp, bkp)
        kb = kp_ref[0, pl.ds(start, bkp), :]
        vb = vp_ref[0, pl.ds(start, bkp), :]
        kj = start + jax.lax.broadcasted_iota(jnp.int32, (1, bkp), 1)
        mask = _local_mask(
            jnp.broadcast_to(kj < plen, (bq, bkp)), q_abs, kj, window, chunk,
            local_on,
        )
        return _online_block(q, kb, vb, mask, *carry, scale, softcap)

    # Blocks past the real prefix are fully masked (the wrapper's zero
    # padding among them) — skip them; with a binding local form, so are
    # blocks wholly before the earliest visible key of this q block's FIRST
    # query.
    first, last = _kv_bounds(
        jnp, plen + qb * bq, bq, bkp, lp // bkp, plen, False, window, chunk,
        local_on,
    )
    m, l, acc = jax.lax.fori_loop(first, last, p_body, (m, l, acc))

    # Own suffix KV: causal within the suffix (distance (plen+qi)-(plen+kj)
    # = qi-kj, so the window clause needs no plen; the chunk clause does).
    ls = ks_ref.shape[2]
    ks = ks_ref[0, 0]
    vs = vs_ref[0, 0]
    kj = jax.lax.broadcasted_iota(jnp.int32, (1, ls), 1)
    mask = _local_mask(kj <= qi, q_abs, plen + kj, window, chunk, local_on)
    m, l, acc = _online_block(q, ks, vs, mask, m, l, acc, scale, softcap)

    o_ref[0, 0] = _finish(l, acc, o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "chunk", "softcap", "interpret"),
)
def flash_prefix_shared_attention(
    q, k_prefix, v_prefix, k_suffix, v_suffix, prefix_len, scale=None,
    window=None, chunk=None, softcap=None, local_on=None, interpret=None,
    sink=None,
):
    """Kernel form of ``ops.attention.prefix_shared_attention``.

    q [S, Ls, n_q, hd]; k_prefix [Lp, n_kv, hd] / v_prefix [Lp, n_kv, dv]
    (SHARED across all suffixes); k_suffix [S, Ls, n_kv, hd] / v_suffix
    [S, Ls, n_kv, dv]; prefix_len int32 scalar. dv == hd everywhere but
    MLA, whose V has its own head dim.
    ``window``/``chunk``/``softcap``/``scale`` mirror the XLA op;
    ``local_on`` is the traced per-layer local toggle (None = on);
    ``sink`` [n_q] a logit per head in the softmax's denominator.
    Returns [S, Ls, n_q, dv].
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s, ls, n_q, hd = q.shape
    lp, n_kv, _ = k_prefix.shape
    if scale is None:
        scale = 1.0 / (hd**0.5)
    # q/k pad together (QK^T dim); v pads on its OWN dim (MLA: 192 vs 128).
    (q, k_prefix, k_suffix), _ = _pad_head_dim(q, k_prefix, k_suffix)
    (v_prefix, v_suffix), dv_true = _pad_head_dim(v_prefix, v_suffix)
    hd, dv = q.shape[-1], v_prefix.shape[-1]
    # Head-major; the suffix rows and the prefix's length zero-padded up to
    # the tiles, as in flash_causal_attention (padded prefix keys sit at
    # kj >= lp >= prefix_len). A suffix's own keys stay whole: one step.
    bq, bkp = flash_tiles(ls, lp, hd, dv, window, chunk)
    q = _pad_dim(q.transpose(0, 2, 1, 3), 2, bq)
    k_prefix = _pad_dim(k_prefix.transpose(1, 0, 2), 1, bkp)
    v_prefix = _pad_dim(v_prefix.transpose(1, 0, 2), 1, bkp)
    lsp, lpp = q.shape[2], k_prefix.shape[1]
    grid = (s, n_q, lsp // bq)
    kv_head = lambda si, h, qb, *_: (h * n_kv // n_q, 0, 0)
    skv_head = lambda si, h, qb, *_: (si, h * n_kv // n_q, 0, 0)
    q_map = lambda si, h, qb, *_: (si, h, qb, 0)
    prefetch = _prefetch(_flags(prefix_len, local_on), sink)

    kernel = functools.partial(
        _prefix_shared_kernel, scale=scale, lp=lpp, bkp=bkp, window=window,
        chunk=chunk, softcap=softcap, has_sink=sink is not None,
    )
    with jax.named_scope("flash_prefix_shared_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, 1, bq, hd), q_map),
                    pl.BlockSpec((1, lpp, hd), kv_head),
                    pl.BlockSpec((1, lpp, dv), kv_head),
                    pl.BlockSpec((1, 1, ls, hd), skv_head),
                    pl.BlockSpec((1, 1, ls, dv), skv_head),
                ],
                out_specs=pl.BlockSpec((1, 1, bq, dv), q_map),
            ),
            out_shape=jax.ShapeDtypeStruct((s, n_q, lsp, dv), q.dtype),
            interpret=interpret,
            name="flash_prefix_shared_attention",
            metadata={"kernel": "flash_prefix_shared_attention"},
        )(
            *prefetch,
            q,
            k_prefix,
            v_prefix,
            k_suffix.transpose(0, 2, 1, 3),
            v_suffix.transpose(0, 2, 1, 3),
        )
    return out[:, :, :ls, :dv_true].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Single-token decode attention over three cached KV regions
# ---------------------------------------------------------------------------

def _decode_kernel(
    flags_ref, *refs, scale, lp, bkp, window, chunk, softcap, g, has_sink,
):
    # Head-major blocks: q_ref [1, 1, gp, hd] (the query group rows of one
    # (suffix, kv-head) program, padded to the sublane multiple);
    # kp_ref [1, lp, hd], vp_ref [1, lp, dv]; ks_ref/kg_ref [1, 1, L, hd],
    # vs_ref/vg_ref [1, 1, L, dv] (dv == hd except where V has its own dim).
    sink_ref, refs = _split_sink(refs, has_sink)
    q_ref, kp_ref, vp_ref, ks_ref, vs_ref, kg_ref, vg_ref, o_ref = refs
    si = pl.program_id(0)
    _, _, gp, hd = q_ref.shape
    dv = vp_ref.shape[-1]
    q = q_ref[0, 0]
    plen = flags_ref[0]
    t = flags_ref[1]
    local_on = flags_ref[2] != 0
    eos = flags_ref[3 + si]
    # The one new token sits at absolute position plen + eos + 1 + t
    # (ops.attention.decode_attention convention).
    q_abs = plen + eos + 1 + t

    sink = None
    if sink_ref is not None:
        # Row j of the group is query head kv_head * g + j; rows past g are
        # the sublane padding (sliced off by the wrapper).
        row = jax.lax.broadcasted_iota(jnp.int32, (gp, 1), 0)
        sink = jnp.zeros((gp, 1), jnp.float32)
        for j in range(g):
            sink = jnp.where(row == j, sink_ref[pl.program_id(1) * g + j], sink)
    m, l, acc = _start(gp, dv, sink)

    # Shared prefix KV: visible iff the key is real (j < plen).
    def p_body(blk, carry):
        m, l, acc = carry
        start = blk * bkp
        kb = kp_ref[0, pl.ds(start, bkp), :]
        vb = vp_ref[0, pl.ds(start, bkp), :]
        kj = start + jax.lax.broadcasted_iota(jnp.int32, (1, bkp), 1)
        mask = _local_mask(
            jnp.broadcast_to(kj < plen, (gp, bkp)), q_abs, kj, window, chunk,
            local_on,
        )
        return _online_block(q, kb, vb, mask, m, l, acc, scale, softcap)

    first, n_real = _kv_bounds(
        jnp, q_abs, 1, bkp, lp // bkp, plen, False, window, chunk, local_on
    )
    m, l, acc = jax.lax.fori_loop(first, n_real, p_body, (m, l, acc))

    # Own suffix KV: keys j <= eos; absolute position plen + j.
    ls = ks_ref.shape[2]
    kj = jax.lax.broadcasted_iota(jnp.int32, (1, ls), 1)
    mask = _local_mask(
        jnp.broadcast_to(kj <= eos, (gp, ls)), q_abs, plen + kj, window,
        chunk, local_on,
    )
    m, l, acc = _online_block(
        q, ks_ref[0, 0], vs_ref[0, 0], mask, m, l, acc, scale, softcap
    )

    # Generated-token KV: keys j <= t (slot t holds this step's own KV);
    # absolute position plen + eos + 1 + j.
    tm = kg_ref.shape[2]
    kj = jax.lax.broadcasted_iota(jnp.int32, (1, tm), 1)
    mask = _local_mask(
        jnp.broadcast_to(kj <= t, (gp, tm)), q_abs, plen + eos + 1 + kj,
        window, chunk, local_on,
    )
    m, l, acc = _online_block(
        q, kg_ref[0, 0], vg_ref[0, 0], mask, m, l, acc, scale, softcap
    )

    o_ref[0, 0] = _finish(l, acc, o_ref.dtype)


def supports_decode(
    n_q: int, n_kv: int, head_dim: int, v_dim: int | None = None
) -> bool:
    """Decode-kernel eligibility: whole query groups and a lane-aligned
    head_dim. Unlike the scoring kernels, ragged head dims DON'T pad here:
    the wrapper would re-pad the entire parked KV cache every layer every
    token — a full-cache HBM round trip added to exactly the bandwidth-bound
    loop the kernel exists to speed up — so those models keep the XLA decode
    op. (Ragged KV lengths still pad; masks exclude the padding.)"""
    return (
        n_q % n_kv == 0
        and head_dim % 128 == 0
        and (v_dim is None or v_dim % 128 == 0)
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "chunk", "softcap", "interpret"),
)
def flash_decode_attention(
    q, k_prefix, v_prefix, k_suffix, v_suffix, k_gen, v_gen, prefix_len,
    suffix_eos, t, scale=None, window=None, chunk=None, softcap=None,
    local_on=None, interpret=None, sink=None,
):
    """Kernel form of ``ops.attention.decode_attention`` — ONE new token per
    suffix attending jointly over [shared prefix KV ; own suffix KV ;
    generated KV] (the KV-cache decode hot loop; the reference re-streams
    the whole prompt per token instead, ``/root/reference/main.py:65-76``).

    q [S, 1, n_q, hd]; k/v_prefix [Lp, n_kv, hd]; k/v_suffix [S, Ls, n_kv, hd];
    k/v_gen [S, T, n_kv, hd]; prefix_len/t int32 scalars; suffix_eos int32 [S];
    the values may have their own head dim dv; ``sink`` [n_q] is a logit per
    head in the softmax's denominator.
    Returns [S, 1, n_q, dv]. Unlike the XLA op, KV blocks past the real
    prefix (and wholly outside a binding window/chunk) are SKIPPED, so a
    short prompt in a long bucket only pays for its real keys.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s, _, n_q, hd = q.shape
    lp, n_kv, _ = k_prefix.shape
    g = n_q // n_kv
    if scale is None:
        scale = 1.0 / (hd**0.5)
    # q/k pad together (QK^T dim); v pads on its OWN dim.
    (q, k_prefix, k_suffix, k_gen), _ = _pad_head_dim(q, k_prefix, k_suffix, k_gen)
    (v_prefix, v_suffix, v_gen), dv_true = _pad_head_dim(v_prefix, v_suffix, v_gen)
    hd, dv = q.shape[-1], v_prefix.shape[-1]

    # Head-major layouts; ragged axes pad up (masks exclude the padding):
    # the query group to the fp32 sublane multiple, KV lengths to the lane
    # tiling. All pads are no-ops at bucketed shapes.
    qg = _pad_dim(q.reshape(s, n_kv, g, hd), 2, 8)
    gp = qg.shape[2]
    kp = _pad_dim(k_prefix.transpose(1, 0, 2), 1, 64)
    vp = _pad_dim(v_prefix.transpose(1, 0, 2), 1, 64)
    ks = _pad_dim(k_suffix.transpose(0, 2, 1, 3), 2, 64)
    vs = _pad_dim(v_suffix.transpose(0, 2, 1, 3), 2, 64)
    kg = _pad_dim(k_gen.transpose(0, 2, 1, 3), 2, 64)
    vg = _pad_dim(v_gen.transpose(0, 2, 1, 3), 2, 64)
    lpp = kp.shape[1]
    bkp = _block(lpp, _MAX_BLOCK_K)

    # Scalar-prefetch payload: [plen, t, local_on, eos_0..eos_{S-1}].
    local_flag = jnp.asarray(True if local_on is None else local_on)
    flags = jnp.concatenate(
        [
            jnp.stack(
                [
                    jnp.asarray(prefix_len, jnp.int32),
                    jnp.asarray(t, jnp.int32),
                    local_flag.astype(jnp.int32),
                ]
            ),
            jnp.asarray(suffix_eos, jnp.int32),
        ]
    )

    grid = (s, n_kv)
    kv_head = lambda si, h, *_: (h, 0, 0)
    skv = lambda si, h, *_: (si, h, 0, 0)
    prefetch = _prefetch(flags, sink)

    kernel = functools.partial(
        _decode_kernel, scale=scale, lp=lpp, bkp=bkp, window=window,
        chunk=chunk, softcap=softcap, g=g, has_sink=sink is not None,
    )
    with jax.named_scope("flash_decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, 1, gp, hd), skv),
                    pl.BlockSpec((1, lpp, hd), kv_head),
                    pl.BlockSpec((1, lpp, dv), kv_head),
                    pl.BlockSpec((1, 1, ks.shape[2], hd), skv),
                    pl.BlockSpec((1, 1, ks.shape[2], dv), skv),
                    pl.BlockSpec((1, 1, kg.shape[2], hd), skv),
                    pl.BlockSpec((1, 1, kg.shape[2], dv), skv),
                ],
                out_specs=pl.BlockSpec((1, 1, gp, dv), skv),
            ),
            out_shape=jax.ShapeDtypeStruct((s, n_kv, gp, dv), q.dtype),
            interpret=interpret,
            name="flash_decode_attention",
            metadata={"kernel": "flash_decode_attention"},
        )(*prefetch, qg, kp, vp, ks, vs, kg, vg)
    return out[:, :, :g, :dv_true].reshape(s, 1, n_q, dv_true)


__all__ = [
    "flash_causal_attention",
    "flash_prefix_shared_attention",
    "flash_decode_attention",
    "flash_tiles",
    "causal_steps",
    "prefix_shared_steps",
    "supports",
    "supports_decode",
]
