"""Pallas flash-attention kernels vs the XLA reference path (interpret mode
on CPU; the compiled path runs on the chip in `chip_smoke.py` and in every
cell of the benchmark, and compiles for it in `tests/test_tpu_compile.py`)."""

import dataclasses

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


# (query heads, KV heads, qk head dim, v head dim): MHA, GQA 4:1 and 8:1, and
# MLA's qk 192 / v 128 with a KV head a query head.
HEADS = [(4, 4, 128, 128), (8, 2, 128, 128), (8, 1, 128, 128), (2, 2, 192, 128)]
# (bucket, valid length). 192: within one tile. 1216 and 320 divide no tile
# the table picks (256 x 512 -> five query tiles over 1280 rows and three key
# tiles over 1536; 192 x 384 over 384): the valid length at the bucket's end
# (every real key in play, the zero-padded tail beside them), inside the last
# real key tile, in the tile before it, and at 1.
LENGTHS = [
    (192, 192), (192, 64), (192, 1),
    (1216, 1216), (1216, 1100), (1216, 1000), (1216, 1),
    (320, 320), (320, 300), (320, 1),
]


@pytest.mark.parametrize("n_q,n_kv,hd,dv", HEADS)
@pytest.mark.parametrize("lq,valid", LENGTHS)
def test_flash_causal_matches_xla(n_q, n_kv, hd, dv, lq, valid):
    rng = np.random.default_rng(0)
    q = _rand(rng, lq, n_q, hd)
    k = _rand(rng, lq, n_kv, hd)
    v = _rand(rng, lq, n_kv, dv)

    got = flash_causal_attention(q, k, v, valid, interpret=True)
    assert got.shape == (lq, n_q, dv)

    kj = jnp.arange(lq)[None, :]
    mask = causal_mask(lq, lq) & (kj < valid)
    want = attention(q, k, v, mask)
    # Padding rows (i >= valid) still see the real prefix keys in both paths,
    # but their values are never consumed downstream — compare valid rows
    # (all of them at valid == lq: what the wrapper pads the keys and values
    # with beyond the bucket must not leak into any).
    assert np.isfinite(np.asarray(got)).all()
    got_v = np.asarray(got)[:valid]
    want_v = np.asarray(want)[:valid]
    np.testing.assert_allclose(got_v, want_v, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_q,n_kv,hd,dv", HEADS)
@pytest.mark.parametrize(
    "lp,plen",
    [(640, 640), (640, 512), (640, 130), (640, 1)] + LENGTHS[3:],
)
def test_flash_prefix_shared_matches_xla(n_q, n_kv, hd, dv, lp, plen):
    rng = np.random.default_rng(1)
    s, ls = 3, 64
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, dv)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, dv)

    got = flash_prefix_shared_attention(q, kp, vp, ks, vs, plen, interpret=True)
    want = prefix_shared_attention(q, kp, vp, ks, vs, jnp.int32(plen))
    assert got.shape == (s, ls, n_q, dv) and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window,chunk", [(128, None), (None, 192)])
@pytest.mark.parametrize("local_on", [None, True, False])
@pytest.mark.parametrize("lq,valid", [(256, 200), (1216, 1100), (320, 300)])
def test_flash_causal_local_forms(window, chunk, local_on, lq, valid):
    """Sliding-window / chunked masks (+ the traced per-layer toggle) match
    the XLA banded mask — the Gemma2/3 / binding-window Mistral / Llama4
    envelope the kernels gained in r3 — also at buckets the tiles do not
    divide (1216, 320), where the window's tiles (128 x 128) and, with the
    toggle off, the same tiles walk the whole triangle."""
    rng = np.random.default_rng(3)
    n_q, n_kv, hd = 4, 2, 128
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


@pytest.mark.parametrize(
    "lp,plen", [(640, 576), (640, 130), (1216, 1100), (1216, 1000), (320, 300)]
)
@pytest.mark.parametrize("window,chunk", [(200, None), (None, 256), (128, None)])
@pytest.mark.parametrize("local_on", [None, False])
def test_flash_prefix_shared_local_forms(lp, plen, window, chunk, local_on):
    """Windowed/chunked prefix-shared attention vs the XLA op, with the
    window binding INSIDE the (dynamic-length) prefix, at buckets the tiles
    divide and do not, and with the traced toggle off."""
    rng = np.random.default_rng(4)
    s, ls, n_q, n_kv, hd = 2, 64, 4, 2, 128
    q = _rand(rng, s, ls, n_q, hd)
    kp = _rand(rng, lp, n_kv, hd)
    vp = _rand(rng, lp, n_kv, hd)
    ks = _rand(rng, s, ls, n_kv, hd)
    vs = _rand(rng, s, ls, n_kv, hd)

    flag = None if local_on is None else jnp.asarray(local_on)
    got = flash_prefix_shared_attention(
        q, kp, vp, ks, vs, plen, window=window, chunk=chunk, local_on=flag,
        interpret=True,
    )
    want = prefix_shared_attention(
        q, kp, vp, ks, vs, jnp.int32(plen), window=window, chunk=chunk,
        sliding=flag,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("shape", ["mha", "mla"])
@pytest.mark.parametrize("window", [None, 128])
def test_flash_scoring_kernels_under_vmap(shape, window):
    """A block's prompts ride ``vmap`` (``_decoder_block``): each prompt its
    own valid length inside one bucket the tiles do not divide, so the pad
    and the slice are batched with the call."""
    rng = np.random.default_rng(8)
    n_q, n_kv, hd, dv = {"mha": (2, 2, 128, 128), "mla": (2, 2, 192, 128)}[shape]
    b, lp, s, ls = 3, 320, 2, 64
    plen = jnp.asarray([320, 290, 1], jnp.int32)
    q = _rand(rng, b, lp, n_q, hd)
    kp = _rand(rng, b, lp, n_kv, hd)
    vp = _rand(rng, b, lp, n_kv, dv)
    qs = _rand(rng, b, s, ls, n_q, hd)
    ks = _rand(rng, b, s, ls, n_kv, hd)
    vs = _rand(rng, b, s, ls, n_kv, dv)

    got_c = jax.vmap(
        lambda q, k, v, n: flash_causal_attention(
            q, k, v, n, window=window, interpret=True)
    )(q, kp, vp, plen)
    got_s = jax.vmap(
        lambda q, k, v, k2, v2, n: flash_prefix_shared_attention(
            q, k, v, k2, v2, n, window=window, interpret=True)
    )(qs, kp, vp, ks, vs, plen)
    kj = jnp.arange(lp)[None, :]
    for i, n in enumerate(np.asarray(plen)):
        want_c = attention(
            q[i], kp[i], vp[i], causal_mask(lp, lp, window=window) & (kj < n)
        )
        np.testing.assert_allclose(
            np.asarray(got_c[i])[:n], np.asarray(want_c)[:n], rtol=2e-5, atol=2e-5
        )
        want_s = prefix_shared_attention(
            qs[i], kp[i], vp[i], ks[i], vs[i], jnp.int32(n), window=window
        )
        np.testing.assert_allclose(
            np.asarray(got_s[i]), np.asarray(want_s), rtol=2e-5, atol=2e-5
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


# --- the tiles, and the host's count of the steps they give -------------------

@pytest.mark.parametrize(
    "call,tiles",
    [
        # The long cells' prefix buckets (Ouro 16:16 and MiniCPM-SALA 32:2 at
        # 128; MiMo's full layers and MLA at qk 192 / v 128): 256 x 512,
        # whatever the bucket, which divides none of them.
        ((1216, 1216, 128, 128), (256, 512)),
        ((1728, 1728, 128, 128), (256, 512)),
        ((2432, 2432, 192, 128), (256, 512)),
        ((3392, 3392, 192, 128), (256, 512)),
        ((4096, 4096, 128, 128), (256, 512)),
        # MiMo's window-128 layers: a query tile of the window, keys of two.
        ((3392, 3392, 192, 128, 128), (128, 256)),
        ((64, 3392, 192, 128, 128), (64, 256)),
        # A window that no longer fits two key tiles is full attention's.
        ((4096, 4096, 128, 128, 1024), (256, 512)),
        ((1216, 1216, 128, 128, None, 200), (256, 512)),  # a chunk of 200
        # A suffix's 64 rows over the prefix: the whole prefix in a step.
        ((64, 3392, 128, 128), (64, 3456)),
        ((64, 4096, 192, 128), (64, 4096)),
        ((128, 4096, 128, 128), (128, 2048)),
        # score-b8's buckets: evenly sized tiles, not a full one and a sliver.
        ((320, 320, 192, 128), (192, 384)),
        ((576, 576, 192, 128), (192, 384)),
        ((768, 768, 192, 128), (256, 384)),
        # Nothing to tile: a length of at most 64 is its own block.
        ((64, 64, 128, 128), (64, 64)),
    ],
)
def test_flash_tiles_table(call, tiles):
    """The tile table is a pure function of a call's static shapes."""
    from flexible_llm_sharding_tpu.ops.pallas_attention import flash_tiles

    assert flash_tiles(*call) == tiles


def _tiles_with_a_visible_key(rows_abs, n_rows_pad, n_keys, plen, bq, bk, causal, window):
    """Count (query tile, key tile) pairs that hold a visible (row, key)
    pair, from the full mask: what the kernels' loop bounds must walk."""
    rows = np.arange(n_rows_pad)[:, None]
    keys = np.arange(-(-n_keys // bk) * bk)[None, :]
    q_abs = rows_abs + rows
    vis = keys < plen
    if causal:
        vis = vis & (keys <= q_abs)
    if window is not None:
        vis = vis & (q_abs - keys < window)
    vis = np.broadcast_to(vis, (n_rows_pad, keys.shape[1]))
    tiled = vis.reshape(n_rows_pad // bq, bq, keys.shape[1] // bk, bk)
    return int(tiled.any(axis=(1, 3)).sum())


@pytest.mark.parametrize("model", ["full", "window", "toggled"])
def test_flash_steps_in_the_sweep_record(model, tmp_path):
    """``flash_steps`` of a toy pass equals the number of (query tile, key
    tile) pairs with a visible key, counted from the full masks by the
    tiles ``flash_tiles`` gives, plus a step a (head, suffix) for the
    suffix's own keys: a window model's layers skip the tiles before the
    window, a model whose layers alternate (one scan, the toggle traced)
    keeps the window's tiles in every layer and skips only in the local
    ones."""
    from flexible_llm_sharding_tpu.config import FrameworkConfig, LlamaConfig
    from flexible_llm_sharding_tpu.models import llama
    from flexible_llm_sharding_tpu.ops.pallas_attention import flash_tiles
    from flexible_llm_sharding_tpu.runtime import executor, orchestration
    from flexible_llm_sharding_tpu.utils.checkpoint import save_params
    from tests.fake_tokenizer import FakeTokenizer

    window = None if model == "full" else 128
    pattern = (True, False) if model == "toggled" else None
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=1024, sliding_window=window,
        layer_sliding=pattern, model_type="llama" if window is None else "mistral",
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    save_params(jax.tree.map(np.asarray, params), str(tmp_path), cfg)
    words = lambda n: " ".join(f"w{i % 50}" for i in range(n))
    prompts = [
        (words(300), (" a b", " c d e")),  # bucket 320: tiles of 192 x 384
        (words(70), (" f",)),  # bucket 128
    ]
    fw = FrameworkConfig(
        model_path=str(tmp_path), layer_num_per_shard=2, storage_location="cpu",
        dtype="float32", use_pallas=True, host_cache_gb=0,
    )
    scores = orchestration.run_prompts(
        fw, prompts, tokenizer=FakeTokenizer(), devices=jax.devices()[:1]
    )
    rec = executor.process_sweep_log()[-1]
    want = orchestration.run_prompts(
        dataclasses.replace(fw, use_pallas=False), prompts,
        tokenizer=FakeTokenizer(), devices=jax.devices()[:1],
    )
    for g, w in zip(scores, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
    assert executor.process_sweep_log()[-1]["flash_steps"] == 0  # the XLA ops

    tok = executor.PromptTokenizer(FakeTokenizer(), fw.max_token_len, fw.bucket_multiple)
    n_q, hd, ls = 2, 64, 64
    expected = 0
    for layer_local in (pattern or (window is not None,) * 2):
        for prefix, suffixes in prompts:
            t = tok(prefix, suffixes)
            lp, plen, s = t.prefix_ids.shape[0], t.prefix_len, t.suffix_ids.shape[0]
            assert lp + ls > 128  # the window binds at both buckets
            w = window if layer_local else None
            bq, bk = flash_tiles(lp, lp, hd, hd, window)
            pad = -(-lp // bq) * bq
            expected += n_q * _tiles_with_a_visible_key(0, pad, lp, plen, bq, bk, True, w)
            bq, bk = flash_tiles(ls, lp, hd, hd, window)
            expected += n_q * s * (
                _tiles_with_a_visible_key(plen, ls, lp, plen, bq, bk, False, w) + 1
            )
    assert rec["flash_steps"] == expected > 0
    assert "flash_steps" in executor.SWEEP_RECORD_HELP
