"""Delta-rule linear attention with a decay per key channel and token (KDA,
GLM-5.3's ``linear_attention`` layers), chunked: the XLA op and the Pallas TPU
kernel of one signature.

The recurrence, per head with a state ``S [d, dv]``, a log-decay ``g_t`` in
``(bound, 0]^d`` and a write strength ``beta_t`` in ``[0, 1]``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Unlike the decayed sum of ``ops/lightning_attention.py`` the update READS the
state: with ``u_t = beta_t (v_t - S_{t-1}^T (k_t * exp(g_t)))`` it is ``S_t =
Diag(exp(g_t)) S_{t-1} + k_t u_t^T``. A row with ``g_t = 0`` and ``beta_t = 0``
leaves the state as it was, which is how a right-padded sequence stops the
clock (``models/llama._kda_prefix_suffix``).

Inside a chunk, with ``G_t`` the log-decay summed from the chunk's first row
through row t, ``A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for
``s < t`` and ``B`` the same with ``q_t`` for ``s <= t``::

    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S)      a triangular solve
    O  = (Q * exp(G)) S + B U
    S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T U

``A`` and ``B`` are matrix products only in the factored form ``(k_t *
exp(G_t)) . (k_s * exp(-G_s))``, whose second factor overflows float32 once
``-G_s`` passes 88. The layer's bound on ``g`` (``gate_lower_bound`` = -5) is
what makes it safe over SUB = 16 rows (80 < 88): a pair of rows in one
16-row sub-block uses the factored form with the sums taken from the
sub-block's middle row (both exponents within +-40, clear of overflow and of
denormals), and a pair in two sub-blocks of one chunk goes through the later
sub-block's start, ``exp(G_t - G_b) * exp(G_b - G_s)`` with both exponents
non-positive.

:func:`kda_attention_xla` walks chunks of 16 rows (one sub-block: no pairs
across) as a ``lax.scan``, with ``solve_triangular``: the CPU path and the
fallback. :func:`kda_attention` is the kernel: one program per (sequence,
head, chunk of 64 rows), the chunk axis sequential with the float32 state in
VMEM scratch (kept transposed, ``[dv, d]``, so that the per-channel decay runs
along lanes); the 16-row diagonal blocks of ``I + Diag(beta) A`` are inverted
by the finite Neumann product of a nilpotent matrix, merged to the chunk's
inverse by two block eliminations, all in float32; the other products go to the MXU in the inputs'
dtype. It reads q, k, v and writes o as ``[N, L, H * d]``, a head's block
straight out of the projections' layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16  # rows over which exp(+-cumsum g) stays inside float32
CHUNK = 64  # the kernel's rows a step: 4 sub-blocks
_HIGHEST = jax.lax.Precision.HIGHEST


def supports(head_dim: int, v_dim: int, length: int) -> bool:
    """Kernel eligibility: square lane-wide heads (beta rides a block of the
    heads' width) and a bucketed length (whole chunks)."""
    return head_dim == v_dim and head_dim % 128 == 0 and length % CHUNK == 0


def _precision(dtype):
    return _HIGHEST if dtype == jnp.float32 else None


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_attention_xla(q, k, v, g, beta, initial_state=None, chunk=SUB):
    """q, k ``[N, L, H, d]`` (q scaled, both normalised by the caller), v
    ``[N, L, H, dv]``, g float32 ``[N, L, H, d]`` (log-decay, <= 0), beta
    ``[N, L, H]``, initial_state float32 ``[H, d, dv]`` (shared by the N
    sequences; None = zeros) -> (o ``[N, L, H, dv]``, final state float32
    ``[N, H, d, dv]``). ``chunk`` <= 16 (see the module's docstring)."""
    n, length, h, d = q.shape
    dv = v.shape[-1]
    c = chunk
    nc = -(-length // c)
    prec = _precision(q.dtype)
    f32 = jnp.float32

    def chunks(a):  # [N, L, H, ...] -> [nc, N, H, c, ...]
        pad = [(0, 0), (0, nc * c - length)] + [(0, 0)] * (a.ndim - 2)
        a = jnp.pad(a.astype(f32), pad).reshape(n, nc, c, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    qs, ks, vs, gs = chunks(q), chunks(k), chunks(v), chunks(g)
    bs = chunks(beta)  # [nc, N, H, c]
    if initial_state is None:
        initial_state = jnp.zeros((h, d, dv), f32)
    s0 = jnp.broadcast_to(initial_state.astype(f32), (n, h, d, dv))
    ii = jnp.arange(c)
    strict, causal = ii[:, None] > ii[None, :], ii[:, None] >= ii[None, :]
    mm = functools.partial(jnp.einsum, precision=prec, preferred_element_type=f32)

    def step(s, xs):
        qc, kc, vc, gc, bc = xs
        big = jnp.cumsum(gc, axis=2)  # [N, H, c, d]
        loc = big - big[:, :, c // 2 - 1 : c // 2]  # from the middle row: +-40
        kn = kc * jnp.exp(-loc)
        a = jnp.where(strict, mm("nhid,nhjd->nhij", kc * jnp.exp(loc), kn), 0.0)
        b = jnp.where(causal, mm("nhid,nhjd->nhij", qc * jnp.exp(loc), kn), 0.0)
        kp, qp = kc * jnp.exp(big), qc * jnp.exp(big)
        rhs = bc[..., None] * (vc - mm("nhid,nhdv->nhiv", kp, s))
        u = jax.scipy.linalg.solve_triangular(
            jnp.eye(c, dtype=f32) + bc[..., None] * a, rhs, lower=True, unit_diagonal=True
        )
        o = mm("nhid,nhdv->nhiv", qp, s) + mm("nhij,nhjv->nhiv", b, u)
        last = big[:, :, -1:, :]  # [N, H, 1, d]
        s = s * jnp.swapaxes(jnp.exp(last), 2, 3) + mm(
            "nhjd,nhjv->nhdv", kc * jnp.exp(last - big), u
        )
        return s, o

    s, o = jax.lax.scan(step, s0, (qs, ks, vs, gs, bs))
    o = jnp.moveaxis(o, 0, 1)  # [N, nc, H, c, dv]
    o = jnp.moveaxis(o, 2, 3).reshape(n, nc * c, h, dv)[:, :length]
    return o.astype(v.dtype), s


def _kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_out_ref, s_scr, *,
    chunk, n_chunks, precision,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[...]

    f32 = jnp.float32
    mxu = q_ref.dtype
    nb = chunk // SUB
    q, k, v = (r[...].astype(f32) for r in (q_ref, k_ref, v_ref))  # [c, d], [c, d], [c, dv]
    big = g_ref[...]  # [c, d] float32, summed from the chunk's first row
    beta = b_ref[...].astype(f32)[:, :1]  # [c, 1]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    rblk, cblk = jnp.right_shift(row, 4), jnp.right_shift(col, 4)
    same = rblk == cblk  # [c, c]: a pair inside one sub-block

    def dot(a, b, dims, prec=precision):
        return jax.lax.dot_general(
            a, b, (dims, ((), ())), precision=prec, preferred_element_type=f32
        )

    def dot32(a, b):  # the solve's products stay float32 whatever the inputs
        return dot(a, b, ((1,), (0,)), _HIGHEST)

    # Pairs inside a sub-block: the sums taken from its middle row (+-40).
    mids = [g_ref[i * SUB + SUB // 2 - 1 : i * SUB + SUB // 2, :] for i in range(nb)]
    base = jnp.zeros_like(big)
    for i, gm in enumerate(mids):
        base = jnp.where(rblk == i, gm, base)
    loc = big - base
    eg = jnp.exp(loc)
    kn = (k * jnp.exp(-loc)).astype(mxu)
    ab = dot(jnp.concatenate([(k * eg).astype(mxu), (q * eg).astype(mxu)]), kn, ((1,), (1,)))
    a = jnp.where(same & (col < row), ab[:chunk], 0.0)  # [c, c]
    b = jnp.where(same & (col <= row), ab[chunk:], 0.0)
    # Pairs across sub-blocks: through the later sub-block's start (the sums
    # through the row before it), both factors exp of something <= 0.
    starts = [g_ref[i * SUB - 1 : i * SUB, :] for i in range(1, nb)]  # [1, d] each
    a_off, b_off = [jnp.zeros((SUB, chunk), f32)], [jnp.zeros((SUB, chunk), f32)]
    for i, gs in enumerate(starts, 1):
        blk = slice(i * SUB, (i + 1) * SUB)
        eg = jnp.exp(big[blk] - gs)
        lhs = jnp.concatenate([(k[blk] * eg).astype(mxu), (q[blk] * eg).astype(mxu)])
        kd = (k * jnp.exp(jnp.minimum(gs - big, 0.0))).astype(mxu)
        p = jnp.where(col < i * SUB, dot(lhs, kd, ((1,), (1,))), 0.0)  # [2 SUB, c]
        a_off.append(p[:SUB])
        b_off.append(p[SUB:])
    a = a + jnp.concatenate(a_off)
    b = b + jnp.concatenate(b_off)

    # (I + Diag(beta) A)^-1. The diagonal blocks: with N = -L strictly lower
    # inside 16 x 16 blocks, N^16 = 0 and (I - N)^-1 = (I + N)(I + N^2)(I +
    # N^4)(I + N^8) exactly: three squarings and three products, a chain five
    # deep where forward substitution is fifteen (the kernel is bound by
    # that chain's latency, not by the MXU's rate). Then two block
    # eliminations.
    lm = beta * a
    eye = jnp.where(row == col, 1.0, 0.0)
    n1 = jnp.where(same, -lm, 0.0)
    n2 = dot32(n1, n1)
    n4 = dot32(n2, n2)
    n8 = dot32(n4, n4)
    inv = dot32(dot32(eye + n1, eye + n2), dot32(eye + n4, eye + n8))
    off = jnp.where(same, 0.0, lm)
    rpair, cpair = jnp.right_shift(row, 5), jnp.right_shift(col, 5)
    for mask in (rpair == cpair, rpair != cpair):
        inv = inv - dot32(dot32(inv, jnp.where(mask, off, 0.0)), inv)

    st = s_scr[...]  # [dv, d]: the state, transposed
    eg = jnp.exp(big)
    rhs = beta * (v - dot((k * eg).astype(mxu), st.astype(mxu), ((1,), (1,))))
    u = dot32(inv, rhs)  # [c, dv]
    o = dot((q * eg).astype(mxu), st.astype(mxu), ((1,), (1,))) + dot(
        b.astype(mxu), u.astype(mxu), ((1,), (0,))
    )
    o_ref[...] = o.astype(o_ref.dtype)
    last = g_ref[chunk - 1 : chunk, :]  # [1, d]
    kw = (k * jnp.exp(last - big)).astype(mxu)
    st = st * jnp.exp(last) + dot(u.astype(mxu), kw, ((0,), (0,)))
    s_scr[...] = st

    @pl.when(ci == n_chunks - 1)
    def _():
        s_out_ref[...] = st


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_attention(q, k, v, g, beta, initial_state=None, interpret=None):
    """The Pallas kernel; arguments and results as :func:`kda_attention_xla`.
    Shapes must pass :func:`supports`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"  # as ops/pallas_attention.py
    n, length, h, d = q.shape
    dv = v.shape[-1]
    c, nc = CHUNK, length // CHUNK
    big = jnp.cumsum(g.astype(jnp.float32).reshape(n, nc, c, h * d), axis=2)
    wide = jnp.broadcast_to(beta.astype(q.dtype)[..., None], (n, length, h, d))
    if initial_state is None:
        initial_state = jnp.zeros((h, d, dv), jnp.float32)
    kernel = functools.partial(
        _kernel, chunk=c, n_chunks=nc, precision=_precision(q.dtype)
    )
    rows = lambda width: pl.BlockSpec((None, c, width), lambda ni, hi, ci: (ni, ci, hi))
    # Named three times over, as the flash kernels are (ops/pallas_attention.py).
    with jax.named_scope("kda_chunk"):
        o, st = pl.pallas_call(
            kernel,
            grid=(n, h, nc),
            in_specs=[
                rows(d), rows(d), rows(dv), rows(d), rows(d),
                pl.BlockSpec((None, dv, d), lambda ni, hi, ci: (hi, 0, 0)),
            ],
            out_specs=[
                rows(dv),
                pl.BlockSpec((None, None, dv, d), lambda ni, hi, ci: (ni, hi, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, length, h * dv), v.dtype),
                jax.ShapeDtypeStruct((n, h, dv, d), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
            name="kda_chunk",
            metadata={"kernel": "kda_chunk"},
        )(
            q.reshape(n, length, h * d),
            k.reshape(n, length, h * d),
            v.reshape(n, length, h * dv),
            big.reshape(n, length, h * d),
            wide.reshape(n, length, h * d),
            jnp.swapaxes(initial_state.astype(jnp.float32), 1, 2),
        )
    return o.reshape(n, length, h, dv), jnp.swapaxes(st, 2, 3)


def kda_recurrence(q, k, v, g, beta, initial_state=None):
    """The recurrence itself, a row at a time in float32: what both chunked
    bodies are tested against. Arguments and results as
    :func:`kda_attention_xla`."""
    n, _, h, d = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    if initial_state is None:
        initial_state = jnp.zeros((h, d, dv), f32)
    s0 = jnp.broadcast_to(initial_state.astype(f32), (n, h, d, dv))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs  # [N, H, d] ..., bt [N, H]
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - mm("nhd,nhdv->nhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, mm("nhd,nhdv->nhv", qt, s)

    t = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)
    s, o = jax.lax.scan(step, s0, (t(q), t(k), t(v), t(g), t(beta)))
    return jnp.moveaxis(o, 0, 1), s
