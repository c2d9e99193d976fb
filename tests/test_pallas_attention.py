"""Pallas flash-attention kernels vs the XLA reference path (interpret mode
on CPU; the compiled path runs on the chip in `chip_smoke.py` and in every
cell of the benchmark, and compiles for it in `tests/test_tpu_compile.py`)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexible_llm_sharding_tpu.ops.attention import (
    attention,
    causal_mask,
    prefix_shared_attention,
)
from flexible_llm_sharding_tpu.ops.pallas_attention import (
    flash_causal_attention,
    flash_prefix_shared_attention,
    supports,
)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def test_supports():
    from flexible_llm_sharding_tpu.ops.pallas_attention import supports_decode

    assert supports(16, 16, 128, 256, 256)
    assert supports(32, 8, 128, 64, 4096)
    assert supports(4, 2, 96, 64, 64)  # ragged head dim >= 64: padded inside
    assert not supports(4, 2, 16, 64, 64)  # tiny head dim: XLA is cheaper
    assert not supports(16, 16, 128, 100, 256)  # ragged length
    assert not supports(15, 4, 128, 64, 64)  # n_q not multiple of n_kv
    # Decode never pads head dims (it would re-pad the parked KV cache
    # every layer every token).
    assert supports_decode(8, 2, 128)
    assert not supports_decode(8, 2, 96)


@pytest.mark.parametrize("hd", [96, 64])
def test_flash_ragged_head_dim(hd):
    """Head dims off the 128-lane multiple (phi3's 96) zero-pad inside the
    wrappers — exact vs the XLA ops on all three kernels."""
    from flexible_llm_sharding_tpu.ops.attention import decode_attention
    from flexible_llm_sharding_tpu.ops.pallas_attention import (
        flash_decode_attention,
    )

    rng = np.random.default_rng(9)
    s, ls, n_q, n_kv, lp, tmax, plen = 2, 64, 4, 2, 128, 2, 100
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)

    got = flash_prefix_shared_attention(q, kp, vp, ks, vs, plen, interpret=True)
    want = prefix_shared_attention(q, kp, vp, ks, vs, jnp.int32(plen))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    kj = jnp.arange(lp)[None, :]
    qc = _rand(rng, lp, n_q, hd)
    got_c = flash_causal_attention(qc, kp, vp, plen, interpret=True)
    want_c = attention(qc, kp, vp, causal_mask(lp, lp) & (kj < plen))
    np.testing.assert_allclose(
        np.asarray(got_c)[:plen], np.asarray(want_c)[:plen], rtol=2e-5, atol=2e-5
    )

    qd = _rand(rng, s, 1, n_q, hd)
    kg = _rand(rng, s, tmax, n_kv, hd)
    vg = _rand(rng, s, tmax, n_kv, hd)
    eos = jnp.asarray([5, 60], jnp.int32)
    got_d = flash_decode_attention(
        qd, kp, vp, ks, vs, kg, vg, jnp.int32(plen), eos, jnp.int32(1),
        interpret=True,
    )
    want_d = decode_attention(
        qd, kp, vp, ks, vs, kg, vg, jnp.int32(plen), eos, jnp.int32(1)
    )
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=2e-5, atol=2e-5)


def test_flash_distinct_v_dim():
    """MLA shapes: q/k at one head dim, V at its own — the scoring kernels
    carry the two dims independently (QK^T over hd, PV over dv), so
    DeepSeek's 192-qk/128-v heads ride the flash path."""
    rng = np.random.default_rng(12)
    s, ls, n_q, n_kv, lp, plen = 2, 64, 4, 4, 128, 90
    hd, dv = 96, 64

    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, dv)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, dv)
    got = flash_prefix_shared_attention(q, kp, vp, ks, vs, plen, interpret=True)
    assert got.shape == (s, ls, n_q, dv)
    want = prefix_shared_attention(q, kp, vp, ks, vs, jnp.int32(plen))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )

    qc = _rand(rng, lp, n_q, hd)
    got_c = flash_causal_attention(qc, kp, vp, plen, interpret=True)
    assert got_c.shape == (lp, n_q, dv)
    kj = jnp.arange(lp)[None, :]
    want_c = attention(qc, kp, vp, causal_mask(lp, lp) & (kj < plen))
    np.testing.assert_allclose(
        np.asarray(got_c)[:plen], np.asarray(want_c)[:plen],
        rtol=2e-5, atol=2e-5,
    )


def test_flash_mla_layer_parity():
    """End-to-end: a DeepSeek-style MLA decoder layer under use_pallas
    equals the XLA path — the flash eligibility gate now admits distinct
    qk/v head dims (per-head decompressed K carries the shared rope key,
    GQA ratio 1)."""
    from flexible_llm_sharding_tpu.config import LlamaConfig
    from flexible_llm_sharding_tpu.models import llama

    cfg = LlamaConfig(
        model_type="deepseek_v3",
        vocab_size=256,
        hidden_size=128,
        intermediate_size=128,
        num_hidden_layers=1,
        num_attention_heads=4,
        num_key_value_heads=4,
        kv_lora_rank=32,
        q_lora_rank=32,
        qk_nope_head_dim=64,
        qk_rope_head_dim=32,  # qk head_dim 96, v 64 — both flash-eligible
        v_head_dim=64,
        rope_interleaved=True,
        query_pre_attn_scalar=96.0,
        max_position_embeddings=512,
    )
    params = llama.init_layer_params(jax.random.PRNGKey(0), cfg)
    lp, s, ls = 128, 2, 64
    rng = np.random.default_rng(3)
    ph = jnp.asarray(rng.standard_normal((lp, cfg.hidden_size)), jnp.float32)
    sh = jnp.asarray(
        rng.standard_normal((s, ls, cfg.hidden_size)), jnp.float32
    )
    plen = 100
    want = llama.prefix_suffix_layer(
        params, cfg, ph, sh, jnp.int32(plen), use_pallas=False
    )
    got = llama.prefix_suffix_layer(
        params, cfg, ph, sh, jnp.int32(plen), use_pallas=True
    )
    # Prefix PADDING rows (i >= plen) legitimately differ: the kernel clamps
    # keys at plen where the XLA prefix pass doesn't mask padding queries —
    # their values are never consumed downstream (next layer's KV at those
    # positions is masked by kj < plen). Same comparison rule as
    # test_flash_causal_matches_xla. Suffix rows compare in full.
    np.testing.assert_allclose(
        np.asarray(got[0])[:plen], np.asarray(want[0])[:plen],
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(got[1]), np.asarray(want[1]), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("n_q,n_kv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("valid", [192, 64, 1])
def test_flash_causal_matches_xla(n_q, n_kv, valid):
    rng = np.random.default_rng(0)
    lq, hd = 192, 128
    q = _rand(rng, lq, n_q, hd)
    k = _rand(rng, lq, n_kv, hd)
    v = _rand(rng, lq, n_kv, hd)

    got = flash_causal_attention(q, k, v, valid, interpret=True)

    kj = jnp.arange(lq)[None, :]
    mask = causal_mask(lq, lq) & (kj < valid)
    want = attention(q, k, v, mask)
    # Padding rows (i >= valid) still see the real prefix keys in both paths,
    # but their values are never consumed downstream — compare valid rows.
    got_v = np.asarray(got)[:valid]
    want_v = np.asarray(want)[:valid]
    np.testing.assert_allclose(got_v, want_v, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("plen", [640, 512, 130, 1])
def test_flash_prefix_shared_matches_xla(plen):
    rng = np.random.default_rng(1)
    s, ls, n_q, n_kv, hd, lp = 3, 64, 8, 2, 128, 640
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)

    got = flash_prefix_shared_attention(q, kp, vp, ks, vs, plen, interpret=True)
    want = prefix_shared_attention(q, kp, vp, ks, vs, jnp.int32(plen))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window,chunk", [(128, None), (None, 192)])
@pytest.mark.parametrize("local_on", [None, True, False])
def test_flash_causal_local_forms(window, chunk, local_on):
    """Sliding-window / chunked masks (+ the traced per-layer toggle) match
    the XLA banded mask — the Gemma2/3 / binding-window Mistral / Llama4
    envelope the kernels gained in r3."""
    rng = np.random.default_rng(3)
    lq, n_q, n_kv, hd, valid = 256, 4, 2, 128, 200
    q = _rand(rng, lq, n_q, hd)
    k = _rand(rng, lq, n_kv, hd)
    v = _rand(rng, lq, n_kv, hd)

    flag = None if local_on is None else jnp.asarray(local_on)
    got = flash_causal_attention(
        q, k, v, valid, window=window, chunk=chunk, local_on=flag,
        interpret=True,
    )
    use_local = local_on is None or local_on
    kj = jnp.arange(lq)[None, :]
    mask = causal_mask(
        lq, lq,
        window=window if use_local else None,
        chunk=chunk if use_local else None,
    ) & (kj < valid)
    want = attention(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(want)[:valid], rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("plen", [576, 130])
@pytest.mark.parametrize("window,chunk", [(200, None), (None, 256)])
def test_flash_prefix_shared_local_forms(plen, window, chunk):
    """Windowed/chunked prefix-shared attention vs the XLA op, with the
    window binding INSIDE the (dynamic-length) prefix."""
    rng = np.random.default_rng(4)
    s, ls, n_q, n_kv, hd, lp = 2, 64, 4, 2, 128, 640
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)

    got = flash_prefix_shared_attention(
        q, kp, vp, ks, vs, plen, window=window, chunk=chunk, interpret=True
    )
    want = prefix_shared_attention(
        q, kp, vp, ks, vs, jnp.int32(plen), window=window, chunk=chunk
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_softcap_and_scale():
    """Gemma2-style attention: softcap + query_pre_attn_scalar scale."""
    rng = np.random.default_rng(5)
    s, ls, n_q, n_kv, hd, lp = 2, 64, 4, 4, 128, 256
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)
    scale, cap = 224.0**-0.5, 50.0

    got = flash_prefix_shared_attention(
        q, kp, vp, ks, vs, 200, scale=scale, softcap=cap, interpret=True
    )
    want = prefix_shared_attention(
        q, kp, vp, ks, vs, jnp.int32(200), scale=scale, softcap=cap
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )

    got_c = flash_causal_attention(
        q[0], kp[:64], vp[:64], 50, scale=scale, softcap=cap, interpret=True
    )
    kj = jnp.arange(64)[None, :]
    want_c = attention(
        q[0], kp[:64], vp[:64], causal_mask(64, 64) & (kj < 50),
        scale=scale, softcap=cap,
    )
    np.testing.assert_allclose(
        np.asarray(got_c)[:50], np.asarray(want_c)[:50], rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("plen,t", [(500, 2), (130, 0)])
@pytest.mark.parametrize("window", [None, 200])
def test_flash_decode_matches_xla(plen, t, window):
    """Flash decode kernel (three-region joint softmax, ragged-length
    padding inside the wrapper) vs ops.attention.decode_attention."""
    from flexible_llm_sharding_tpu.ops.attention import decode_attention
    from flexible_llm_sharding_tpu.ops.pallas_attention import (
        flash_decode_attention,
    )

    rng = np.random.default_rng(6)
    s, ls, n_q, n_kv, hd, lp, tmax = 3, 48, 8, 2, 128, 576, 3
    q = _rand(rng, s, 1, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)
    kg = _rand(rng, s, tmax, n_kv, hd)
    vg = _rand(rng, s, tmax, n_kv, hd)
    eos = jnp.asarray([5, 47, 20], jnp.int32)

    got = flash_decode_attention(
        q, kp, vp, ks, vs, kg, vg, jnp.int32(plen), eos, jnp.int32(t),
        window=window, interpret=True,
    )
    want = decode_attention(
        q, kp, vp, ks, vs, kg, vg, jnp.int32(plen), eos, jnp.int32(t),
        window=window,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_decode_under_vmap_scan():
    """The decode runtime runs the kernel inside vmap (block axis) + scan
    (layer axis) — the exact composition _decode_decoders uses."""
    from flexible_llm_sharding_tpu.ops.attention import decode_attention
    from flexible_llm_sharding_tpu.ops.pallas_attention import (
        flash_decode_attention,
    )

    rng = np.random.default_rng(7)
    b, s, ls, n_q, n_kv, hd, lp, tmax = 2, 2, 64, 4, 4, 128, 128, 2
    q = _rand(rng, b, s, 1, n_q, hd)
    kp = _rand(rng, b, lp, n_kv, hd)
    vp = _rand(rng, b, lp, n_kv, hd)
    ks = _rand(rng, b, s, ls, n_kv, hd)
    vs = _rand(rng, b, s, ls, n_kv, hd)
    kg = _rand(rng, b, s, tmax, n_kv, hd)
    vg = _rand(rng, b, s, tmax, n_kv, hd)
    plen = jnp.asarray([100, 64], jnp.int32)
    eos = jnp.asarray([[3, 60], [10, 2]], jnp.int32)
    t = jnp.int32(1)

    f = lambda fn: jax.vmap(
        lambda *a: fn(*a, t, interpret=True)
        if fn is flash_decode_attention
        else fn(*a, t)
    )(q, kp, vp, ks, vs, kg, vg, plen, eos)
    got = f(flash_decode_attention)
    want = f(decode_attention)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_bf16():
    rng = np.random.default_rng(2)
    s, ls, n_q, n_kv, hd, lp = 2, 64, 4, 4, 128, 128
    mk = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
    q, kp, vp = mk(s, ls, n_q, hd), mk(lp, n_kv, hd), mk(lp, n_kv, hd)
    ks, vs = mk(s, ls, n_kv, hd), mk(s, ls, n_kv, hd)
    got = flash_prefix_shared_attention(q, kp, vp, ks, vs, 100, interpret=True)
    want = prefix_shared_attention(q, kp, vp, ks, vs, jnp.int32(100))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )
