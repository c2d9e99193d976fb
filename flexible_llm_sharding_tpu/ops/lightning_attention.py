"""Decayed linear attention (Lightning Attention-2), chunked: the XLA op and
the Pallas TPU kernel of one signature.

The recurrence, per head with a state ``S [d, dv]`` and a per-row
log-decay ``g_t <= 0``::

    S_t = exp(g_t) S_{t-1} + k_t^T v_t        o_t = scale * q_t S_t

A linear-attention layer's decay is one rate per head (``g_t = -s``); the
per-row form is what lets a right-padded sequence stop the clock: rows past
the real length carry ``g_t = 0`` and a zeroed ``k_t``, so they neither decay
the state nor add to it, and the state at the bucket's end is the state at
the real length (``models/llama.prefix_suffix_layer``: the prefix's state,
from which every suffix continues).

Both bodies walk the sequence in chunks of ``chunk`` rows. Inside a chunk,
with ``G_i`` the log-decay summed from the chunk's first row through row i::

    o_i = scale * (sum_{j <= i} exp(G_i - G_j) (q_i . k_j) v_j + exp(G_i) q_i S)
    S'  = exp(G_last) S + sum_j exp(G_last - G_j) k_j^T v_j

Every factor is ``exp`` of a non-positive number: the factored form
``exp(G_i) * exp(-G_j)`` overflows float32 at a rate of 0.84 over a 256-row
chunk. The state is float32 and crosses chunks in float32; the products go to
the MXU in the inputs' dtype (float32 inputs at HIGHEST precision, bit-near the
quadratic form; bfloat16 inputs round the masked scores and the state to
bfloat16 for their read-out, as the flash kernels round their probabilities).

:func:`lightning_attention` is the kernel: one program per (sequence, head,
chunk), the chunk axis sequential with the state in VMEM scratch. It reads
q, k, v and writes o as ``[N, L, H * d]``, a head's ``[chunk, d]`` block
straight out of the projections' layout (no head-major transpose), and takes
the chunk-local ``G`` as a lane-major row. A sequence whose length is no
multiple of ``chunk`` ends in a partial block whose rows past the end are
zeroed in the kernel. :func:`lightning_attention_xla` is the same
mathematics as a ``lax.scan`` over chunks: the CPU path, and the fallback where
:func:`supports` says no.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 256
_NEG = -1e30  # exp(_NEG) == 0: the masked entries of exp(G_i - G_j)
_HIGHEST = jax.lax.Precision.HIGHEST


def supports(head_dim: int, v_dim: int, length: int) -> bool:
    """Kernel eligibility: lane-wide heads and a bucketed length (the
    chunk's rows are sublane multiples; a length under one chunk is one
    block as long as the sequence)."""
    return head_dim % 128 == 0 and v_dim % 128 == 0 and length % 64 == 0


def _chunk(length: int, chunk: int) -> int:
    return min(chunk, length)


def _chunk_log_decay(log_decay: jax.Array, chunk: int) -> jax.Array:
    """``[L, H]`` per-row log-decay -> float32 ``[n_chunks, chunk, H]``
    summed from each chunk's first row (rows past L: 0, the sum stays)."""
    length, h = log_decay.shape
    n = -(-length // chunk)
    g = jnp.pad(log_decay.astype(jnp.float32), ((0, n * chunk - length), (0, 0)))
    return jnp.cumsum(g.reshape(n, chunk, h), axis=1)


def _precision(dtype):
    return _HIGHEST if dtype == jnp.float32 else None


def _default_scale(scale, head_dim: int) -> float:
    return head_dim ** -0.5 if scale is None else scale


@functools.partial(jax.jit, static_argnames=("scale", "chunk"))
def lightning_attention_xla(
    q, k, v, log_decay, initial_state=None, scale=None, chunk=CHUNK,
):
    """q, k ``[N, L, H, d]``, v ``[N, L, H, dv]``, log_decay ``[L, H]`` (one
    clock for the N sequences), initial_state float32 ``[H, d, dv]`` (shared by
    the N sequences; None = zeros) -> (o ``[N, L, H, dv]``, final state
    float32 ``[N, H, d, dv]``)."""
    n, length, h, d = q.shape
    dv = v.shape[-1]
    scale = _default_scale(scale, d)
    c = _chunk(length, chunk)
    g = _chunk_log_decay(log_decay, c)  # [nc, c, H]
    nc = g.shape[0]
    prec = _precision(q.dtype)

    def chunks(a):  # [N, L, H, x] -> [nc, N, c, H, x]
        a = jnp.pad(a, ((0, 0), (0, nc * c - length), (0, 0), (0, 0)))
        return a.reshape(n, nc, c, h, a.shape[-1]).swapaxes(0, 1)

    causal = jnp.tril(jnp.ones((c, c), bool))
    if initial_state is None:
        initial_state = jnp.zeros((h, d, dv), jnp.float32)
    s0 = jnp.broadcast_to(initial_state.astype(jnp.float32), (n, h, d, dv))

    def step(s, xs):
        qc, kc, vc, gc = xs  # [N, c, H, x], gc [c, H]
        gh = gc.T  # [H, c]
        a = jnp.exp(jnp.where(causal, gh[:, :, None] - gh[:, None, :], _NEG))
        p = jnp.einsum(
            "nihd,njhd->nhij", qc, kc, precision=prec,
            preferred_element_type=jnp.float32,
        ) * a
        intra = jnp.einsum(
            "nhij,njhv->nihv", p.astype(vc.dtype), vc, precision=prec,
            preferred_element_type=jnp.float32,
        )
        inter = jnp.einsum(
            "nihd,nhdv->nihv", qc, s.astype(qc.dtype), precision=prec,
            preferred_element_type=jnp.float32,
        ) * jnp.exp(gc)[None, :, :, None]
        last = gc[-1]  # [H]
        kw = (kc.astype(jnp.float32) * jnp.exp(last - gc)[None, :, :, None]).astype(kc.dtype)
        s = s * jnp.exp(last)[None, :, None, None] + jnp.einsum(
            "njhd,njhv->nhdv", kw, vc, precision=prec,
            preferred_element_type=jnp.float32,
        )
        return s, ((intra + inter) * scale).astype(vc.dtype)

    s, o = jax.lax.scan(step, s0, (chunks(q), chunks(k), chunks(v), g))
    o = o.swapaxes(0, 1).reshape(n, nc * c, h, dv)[:, :length]
    return o, s


def _kernel(
    q_ref, k_ref, v_ref, g_ref, s0_ref, o_ref, s_out_ref, s_scr, *,
    scale, length, chunk, n_chunks, precision,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[...]

    q, k, v = q_ref[...], k_ref[...], v_ref[...]  # [c, d], [c, d], [c, dv]
    if length % chunk:
        # The last block hangs over the sequence's end: what it read there is
        # nobody's. (Its rows of the output are not stored.)
        live = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < length
        k = jnp.where(live, k, jnp.zeros_like(k))
        v = jnp.where(live, v, jnp.zeros_like(v))
    g_row = g_ref[...]  # [1, c] float32, summed from the chunk's first row
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # The same sums as a column: the diagonal of the row broadcast down.
    g_col = jnp.sum(jnp.where(ii == jj, g_row, 0.0), axis=1, keepdims=True)  # [c, 1]
    decay = jnp.exp(jnp.where(jj <= ii, g_col - g_row, _NEG))
    p = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    ) * decay
    s = s_scr[...]
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    ) + jnp.exp(g_col) * jax.lax.dot_general(
        q, s.astype(q.dtype), (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (o * scale).astype(o_ref.dtype)
    g_last = jnp.min(g_row, axis=1, keepdims=True)  # [1, 1]: the sums only fall
    kw = (k.astype(jnp.float32) * jnp.exp(g_last - g_col)).astype(k.dtype)
    s = s * jnp.exp(g_last) + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )
    s_scr[...] = s

    @pl.when(ci == n_chunks - 1)
    def _():
        s_out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def lightning_attention(
    q, k, v, log_decay, initial_state=None, scale=None, chunk=CHUNK,
    interpret=None,
):
    """The Pallas kernel; arguments and results as
    :func:`lightning_attention_xla`. Shapes must pass :func:`supports`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"  # as ops/pallas_attention.py
    n, length, h, d = q.shape
    dv = v.shape[-1]
    scale = _default_scale(scale, d)
    c = _chunk(length, chunk)
    g = _chunk_log_decay(log_decay, c)  # [nc, c, H]
    nc = g.shape[0]
    g = g.reshape(nc * c, h).T[:, None, :]  # [H, 1, nc * c]: a chunk is a lane-major row
    if initial_state is None:
        initial_state = jnp.zeros((h, d, dv), jnp.float32)
    kernel = functools.partial(
        _kernel, scale=scale, length=length, chunk=c, n_chunks=nc,
        precision=_precision(q.dtype),
    )
    rows = lambda width: pl.BlockSpec((None, c, width), lambda ni, hi, ci: (ni, ci, hi))
    # Named three times over, as the flash kernels are (ops/pallas_attention.py).
    with jax.named_scope("lightning_attention"):
        o, s = pl.pallas_call(
            kernel,
            grid=(n, h, nc),
            in_specs=[
                rows(d), rows(d), rows(dv),
                pl.BlockSpec((None, 1, c), lambda ni, hi, ci: (hi, 0, ci)),
                pl.BlockSpec((None, d, dv), lambda ni, hi, ci: (hi, 0, 0)),
            ],
            out_specs=[
                rows(dv),
                pl.BlockSpec((None, None, d, dv), lambda ni, hi, ci: (ni, hi, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, length, h * dv), v.dtype),
                jax.ShapeDtypeStruct((n, h, d, dv), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
            name="lightning_attention",
            metadata={"kernel": "lightning_attention"},
        )(
            q.reshape(n, length, h * d),
            k.reshape(n, length, h * d),
            v.reshape(n, length, h * dv),
            g,
            initial_state.astype(jnp.float32),
        )
    return o.reshape(n, length, h, dv), s
