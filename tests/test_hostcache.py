"""Host-path streaming overhaul suite (PR 5): the host-resident shard
cache, the on-device cast, and amortized integrity hashing.

The contract under test: a warm weight-stream sweep performs ZERO host
per-byte work — no numpy dtype cast (deferred to one jitted on-chip
convert), no redundant crc pass (verdicts cached per file generation),
no disk read/parse/stack (host shard cache) — while outputs stay
bit-identical to the cache-off path, and PR 4's corruption detection and
self-healing still fire: stale entries are invalidated on file change,
quarantine purges both caches, and chaos-injected corruption is caught
exactly as before (injected loads bypass the verdict cache).
"""

import os

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import (
    FaultConfig,
    FrameworkConfig,
    ServeConfig,
)
from flexible_llm_sharding_tpu.faults.inject import FaultInjector
from flexible_llm_sharding_tpu.faults.retry import RetryPolicy
from flexible_llm_sharding_tpu.integrity import manifest as iman
from flexible_llm_sharding_tpu.integrity.manifest import ShardCorruptError
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import hostcache
from flexible_llm_sharding_tpu.runtime.executor import (
    StreamingExecutor,
    _HostShardLoader,
    _place,
    np_dtype_for,
)
from flexible_llm_sharding_tpu.runtime.hostcache import HostShardCache
from flexible_llm_sharding_tpu.serve import ServeEngine
from flexible_llm_sharding_tpu.utils.checkpoint import (
    layer_names_for,
    save_params,
)

from tests.fake_tokenizer import FakeTokenizer

CHAOS_SEED = int(os.environ.get("FLS_CHAOS_SEED", "1234"))

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Two plus two equals", (" four", " five")),
    ("The sky is", (" blue", " green")),
    ("Hello world", (" again", " anew")),
]


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_hostcache")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d)


@pytest.fixture(autouse=True)
def _fresh_process_cache():
    hostcache.reset_process_cache()
    iman.reset_verdicts()
    yield
    hostcache.reset_process_cache()


def _fw(model_dir, **kw) -> FrameworkConfig:
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        io_retry_attempts=8,
        io_retry_base_s=0.001,
    )
    base.update(kw)
    return FrameworkConfig(**base)


@pytest.fixture(scope="module")
def clean_scores(model_dir):
    """Fault-free, cache-off oracle shared by the parity tests."""
    return StreamingExecutor(
        _fw(model_dir, host_cache_gb=0.0), tokenizer=FakeTokenizer()
    )(list(PROMPTS))


def _loader(model_dir, cache=None, np_dtype=np.float32, **kw):
    names = layer_names_for(4, tie_word_embeddings=False)
    return _HostShardLoader(
        model_dir,
        names,
        np.dtype(np_dtype),
        retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.0),
        host_cache=cache,
        **kw,
    )


def _flip_bit_in_file(path: str, offset_from_end: int = 100) -> bytes:
    """Flip one bit in place; returns the original byte for repair."""
    size = os.path.getsize(path)
    pos = max(0, size - offset_from_end)
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0x01]))
    return b


def _restore_byte(path: str, b: bytes, offset_from_end: int = 100) -> None:
    size = os.path.getsize(path)
    pos = max(0, size - offset_from_end)
    with open(path, "r+b") as f:
        f.seek(pos)
        f.write(b)


def _tree_equal(a, b) -> None:
    for (_, ga), (_, gb) in zip(a, b):
        la, lb = jax.tree.leaves(ga), jax.tree.leaves(gb)
        assert len(la) == len(lb)
        for xa, xb in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ---------------------------------------------------------------------------
# HostShardCache unit behaviour
# ---------------------------------------------------------------------------

def test_eviction_under_tiny_byte_budget(tmp_path):
    f = str(tmp_path / "w.bin")
    with open(f, "wb") as fh:
        fh.write(b"x" * 64)
    cache = HostShardCache(budget_bytes=1000)
    seg = lambda n: [("decoders", {"layers": np.zeros(n, np.uint8)})]  # noqa: E731
    assert cache.put("a", seg(400), [f])
    assert cache.put("b", seg(400), [f])
    # Third entry exceeds the budget: LRU ("a") must go.
    assert cache.put("c", seg(400), [f])
    s = cache.stats()
    assert s["evictions"] == 1 and s["entries"] == 2
    assert s["bytes"] <= 1000
    assert cache.get("a") is None  # evicted
    assert cache.get("b") is not None and cache.get("c") is not None
    # Recency: touching "b" makes "c" the LRU victim.
    cache.get("b")
    assert cache.put("d", seg(400), [f])
    assert cache.get("c") is None and cache.get("b") is not None
    # An entry larger than the whole budget is refused outright.
    assert not cache.put("huge", seg(4000), [f])
    # Budget shrink re-evicts down to the new bound.
    cache.set_budget(400)
    assert cache.stats()["bytes"] <= 400


def test_stat_guard_invalidates_on_file_change(tmp_path):
    f = str(tmp_path / "w.bin")
    with open(f, "wb") as fh:
        fh.write(b"x" * 256)
    cache = HostShardCache(budget_bytes=1 << 20)
    assert cache.put("k", [("embed", {"x": np.ones(4)})], [f])
    assert cache.get("k") is not None
    import time

    time.sleep(0.05)  # outrun coarse filesystem mtime granularity
    _flip_bit_in_file(f, 10)  # any write updates mtime
    assert cache.get("k") is None  # stale entry dropped, not served
    assert cache.stats()["invalidations"] == 1


# ---------------------------------------------------------------------------
# Loader integration: hits, parity, quarantine, manifest change
# ---------------------------------------------------------------------------

def test_loader_cache_hits_are_bit_identical(model_dir):
    cache = HostShardCache(budget_bytes=1 << 30)
    cached = _loader(model_dir, cache=cache)
    plain = _loader(model_dir)
    idxs = tuple(range(len(plain.layer_names)))
    want = plain.build_host_shard(idxs)
    first = cached.build_host_shard(idxs)
    second = cached.build_host_shard(idxs)  # served from cache
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1
    assert second is first  # the pinned tree itself, no rebuild
    _tree_equal(first, want)
    # Streamed-bytes witness keeps counting on hits (the link still moves
    # the bytes every sweep; only host CPU work is skipped).
    assert cached.bytes_loaded == 2 * plain.bytes_loaded
    cached.close()
    plain.close()


def test_three_sweeps_of_one_loader_hit_two_in_three(model_dir):
    """A shard a layer, three sweeps, a budget that evicts nothing: sweep 1
    misses every shard and sweeps 2 and 3 hit every one, so the cache's own
    cumulative rate reads exactly 2/3 (lower would mean an eviction or a
    key that changed between sweeps)."""
    cache = HostShardCache(budget_bytes=1 << 30)
    loader = _loader(model_dir, cache=cache)
    n = len(loader.layer_names)
    for _ in range(3):
        for i in range(n):
            loader.build_host_shard((i,))
    loader.close()
    s = cache.stats()
    assert (s["misses"], s["hits"], s["evictions"]) == (n, 2 * n, 0)
    assert s["hit_rate"] == pytest.approx(2 / 3, abs=1e-4)


def test_quarantine_purges_cache_and_verdicts(model_dir):
    cache = HostShardCache(budget_bytes=1 << 30)
    clean = _loader(model_dir, cache=cache)
    clean.build_host_shard((1,))  # layer_names[1] == "model.layers.0"
    assert cache.stats()["entries"] == 1
    # A second loader sharing the cache proves the SAME file persistently
    # corrupt (in-memory injection at rate 1.0, 2 attempts) -> quarantine
    # must purge the cached entry built from that file.
    flaky = _loader(
        model_dir,
        cache=cache,
        injector=FaultInjector.from_config(
            FaultConfig(
                enabled=True, seed=CHAOS_SEED, error_rate=1.0,
                sites=("corrupt_shard",),
            )
        ),
    )
    with pytest.raises(ShardCorruptError, match="quarantined"):
        flaky._load_one(clean.layer_names[1])
    assert cache.stats()["entries"] == 0
    # The crc verdict for the quarantined path is gone too: a fresh
    # UNINJECTED load re-verifies from scratch (full_verifies increments).
    before = iman.verdict_stats()["full_verifies"]
    clean._load_one(clean.layer_names[1])
    assert iman.verdict_stats()["full_verifies"] > before
    clean.close()
    flaky.close()


def test_manifest_change_invalidates_cache_keys(model_dir, tiny_cfg, tmp_path):
    import shutil

    d = str(tmp_path / "copy")
    shutil.copytree(model_dir, d)
    cfg = _fw(d, host_cache_gb=1.0)
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    want = ex(list(PROMPTS))
    assert ex.stats["host_cache_misses"] > 0
    # Re-prepare the dir in place: new weights, new manifest. A stale
    # cache entry served here would produce the OLD scores.
    params = llama.init_params(jax.random.PRNGKey(1), tiny_cfg)
    save_params(jax.tree.map(np.asarray, params), d, tiny_cfg)
    ex2 = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex2(list(PROMPTS))
    assert ex2.stats["host_cache_hits"] == 0  # every key missed
    assert any(
        not np.array_equal(g, w) for g, w in zip(got, want)
    ), "re-prepared weights must change the scores (stale cache served?)"


# ---------------------------------------------------------------------------
# Warm-sweep invariant: zero host casts, zero redundant crc, full hits
# ---------------------------------------------------------------------------

def test_warm_sweep_zero_host_work_and_parity(model_dir, clean_scores):
    from flexible_llm_sharding_tpu.runtime import executor as ex_mod

    ex_mod.reset_process_streamed_bytes()
    cfg = _fw(model_dir, host_cache_gb=1.0, prefetch_depth=1)
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    first = ex(list(PROMPTS))
    s1 = dict(ex.stats)
    warm = ex(list(PROMPTS))
    s2 = dict(ex.stats)
    for g, w in zip(first, clean_scores):
        np.testing.assert_array_equal(g, w)
    for g, w in zip(warm, clean_scores):
        np.testing.assert_array_equal(g, w)
    # Cold sweep: all misses, every file fully verified once.
    assert s1["host_cache_misses"] > 0 and s1["host_cache_hits"] == 0
    assert s1.get("crc_full_verifies", 0) > 0
    # Warm sweep: all hits, no disk parse, no crc pass, no host cast.
    assert s2["host_cache_hit_rate"] == 1.0
    assert s2["host_cache_misses"] == 0
    assert "crc_full_verifies" not in s2, s2
    assert ex_mod.process_host_casts() == 0
    assert "host_casts" not in s2
    # The streamed-bytes witness still covers BOTH sweeps (the link moves
    # the model every sweep; only the host-side work is amortized).
    assert s2["streamed_bytes"] == s1["streamed_bytes"] > 0


def test_verdict_cache_amortizes_without_shard_cache(model_dir):
    """crc verdicts amortize independently of the shard cache: with the
    cache OFF, sweep 2 re-reads the files but skips the hash pass."""
    cfg = _fw(model_dir, host_cache_gb=0.0)
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    ex(list(PROMPTS))
    ex(list(PROMPTS))
    s2 = ex.stats
    assert "host_cache_hits" not in s2  # cache disabled
    assert s2.get("crc_verdict_hits", 0) > 0
    assert "crc_full_verifies" not in s2, s2


# ---------------------------------------------------------------------------
# Self-healing composition: rot invalidates, never serves stale bytes
# ---------------------------------------------------------------------------

def test_on_disk_rot_invalidates_instead_of_serving_stale(model_dir, tmp_path):
    import shutil

    d = str(tmp_path / "rot")
    shutil.copytree(model_dir, d)
    cfg = _fw(d, host_cache_gb=1.0)
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    ex(list(PROMPTS))  # warm the cache with verified-clean trees
    target = os.path.join(d, "model.layers.1.safetensors")
    orig = _flip_bit_in_file(target)
    # The cached (GOOD) bytes must NOT mask the on-disk rot: the stat
    # guard forces a re-read, the checksum catches it, re-reads can't
    # heal a persistent flip, and the typed quarantine error surfaces.
    ex2 = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    with pytest.raises(ShardCorruptError):
        ex2(list(PROMPTS))
    cache = hostcache.cache_for(cfg)
    assert cache.stats()["invalidations"] >= 1
    # Repair the file: a fresh executor re-verifies, re-caches, and the
    # scores come back clean.
    _restore_byte(target, orig)
    ex3 = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex3(list(PROMPTS))
    want = StreamingExecutor(
        _fw(d, host_cache_gb=0.0), tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# Chaos parity: cache on + injected corruption stays token-identical
# ---------------------------------------------------------------------------

def test_offline_chaos_parity_with_cache_on(model_dir, clean_scores):
    cfg = _fw(
        model_dir,
        host_cache_gb=1.0,  # explicit budget overrides chaos auto-off
        faults=FaultConfig(
            enabled=True, seed=CHAOS_SEED, error_rate=0.1,
            sites=("corrupt_shard",),
        ),
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    cache = hostcache.cache_for(cfg)
    assert cache is not None
    fired = False
    for _ in range(8):
        got = ex(list(PROMPTS))
        for g, w in zip(got, clean_scores):
            np.testing.assert_array_equal(g, w)
        if ex._injector.count() > 0:
            fired = True
            break
        # Injection draws happen on cache MISSES (a hit skips the read
        # path, as designed); re-arm the schedule by clearing the cache
        # so every loop iteration draws afresh.
        cache.clear()
    assert fired, "the corruption schedule never fired"
    # One final WARM pass over the now-verified cache: still identical.
    got = ex(list(PROMPTS))
    for g, w in zip(got, clean_scores):
        np.testing.assert_array_equal(g, w)
    assert ex.stats["host_cache_hit_rate"] == 1.0


def test_serve_parity_and_stats_with_cache(model_dir, clean_scores):
    cfg = _fw(model_dir, host_cache_gb=1.0, prefetch_depth=1)
    engine = ServeEngine(
        cfg,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=1),
        tokenizer=FakeTokenizer(),
    )
    try:
        for _ in range(2):  # round 2+ sweeps hit the cache
            reqs = [engine.submit(p, s) for p, s in PROMPTS]
            results = [r.future.result(timeout=300) for r in reqs]
            assert engine.error is None
            for res, want in zip(results, clean_scores):
                assert (
                    res.scores[:, 0].argmax(-1) == want[:, 0].argmax(-1)
                ).all()
    finally:
        engine.shutdown(drain=True)
    stats = engine.stats()
    assert stats["host_cache_hit_rate"] > 0, stats
    assert stats["host_cache"]["hits"] > 0


def test_serve_chaos_parity_with_cache(model_dir, clean_scores):
    cfg = _fw(
        model_dir,
        host_cache_gb=1.0,
        prefetch_depth=1,
        faults=FaultConfig(
            enabled=True, seed=CHAOS_SEED, error_rate=0.2,
            sites=("corrupt_shard",),
        ),
    )
    engine = ServeEngine(
        cfg,
        ServeConfig(max_wave_requests=2, default_max_new_tokens=1),
        tokenizer=FakeTokenizer(),
    )
    cache = engine._host_cache
    assert cache is not None
    try:
        for _ in range(6):
            reqs = [engine.submit(p, s) for p, s in PROMPTS]
            results = [r.future.result(timeout=300) for r in reqs]
            assert engine.error is None
            for res, want in zip(results, clean_scores):
                assert (
                    res.scores[:, 0].argmax(-1) == want[:, 0].argmax(-1)
                ).all()
            if engine.metrics.integrity.total("integrity_failures"):
                break
            cache.clear()  # re-arm the miss-path draws (see offline test)
    finally:
        engine.shutdown(drain=True)
    assert engine.metrics.integrity.total("integrity_failures") > 0


# ---------------------------------------------------------------------------
# On-device cast
# ---------------------------------------------------------------------------

def test_device_cast_matches_host_cast_bit_exact(model_dir):
    """fp32-stored weights at fp16 compute: the deferred on-chip convert
    must produce bit-identical placed trees to the host astype path (both
    round to nearest even), with zero host casts on the deferred arm."""
    idxs = (1,)
    dev = _loader(model_dir, np_dtype=np.float16)  # device_cast default on
    host = _loader(model_dir, np_dtype=np.float16, device_cast=False)
    d_placed = _place(dev.build_host_shard(idxs), None, np_dtype=dev.np_dtype)
    h_placed = _place(host.build_host_shard(idxs), None, np_dtype=host.np_dtype)
    assert dev.host_casts == 0
    assert host.host_casts > 0
    for (_, gd), (_, gh) in zip(d_placed, h_placed):
        for xd, xh in zip(jax.tree.leaves(gd), jax.tree.leaves(gh)):
            assert xd.dtype == xh.dtype
            np.testing.assert_array_equal(np.asarray(xd), np.asarray(xh))
    dev.close()
    host.close()


def test_bf16_executor_parity_cache_on_off(model_dir):
    """End-to-end at a CASTING dtype (fp32 store -> bf16 compute): cache
    on vs off bit-identical, no host casts either way."""
    from flexible_llm_sharding_tpu.runtime import executor as ex_mod

    ex_mod.reset_process_streamed_bytes()
    off = StreamingExecutor(
        _fw(model_dir, dtype="bfloat16", host_cache_gb=0.0),
        tokenizer=FakeTokenizer(),
    )(list(PROMPTS))
    ex = StreamingExecutor(
        _fw(model_dir, dtype="bfloat16", host_cache_gb=1.0),
        tokenizer=FakeTokenizer(),
    )
    ex(list(PROMPTS))
    on = ex(list(PROMPTS))  # warm
    assert ex.stats["host_cache_hit_rate"] == 1.0
    assert ex_mod.process_host_casts() == 0
    for g, w in zip(on, off):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# Satellite knobs
# ---------------------------------------------------------------------------

def test_score_sink_cap_threads_through_config(model_dir, clean_scores):
    # Cap 1 forces the rotation path on every block; outputs unchanged.
    got = StreamingExecutor(
        _fw(model_dir, score_sink_max_device=1, host_cache_gb=0.0),
        tokenizer=FakeTokenizer(),
    )(list(PROMPTS))
    for g, w in zip(got, clean_scores):
        np.testing.assert_array_equal(g, w)
    with pytest.raises(ValueError, match="score_sink_max_device"):
        _fw(model_dir, score_sink_max_device=0)


def test_readahead_threads_knob_and_idempotent_close(model_dir):
    loader = _loader(model_dir, readahead_threads=1)
    loader.warm((0, 1))
    loader.close()
    loader.close()  # idempotent
    loader.warm((2,))  # no-op after close, must not raise
    with pytest.raises(ValueError, match="readahead_threads"):
        _fw(model_dir, readahead_threads=0)
    with pytest.raises(ValueError, match="host_cache_gb"):
        _fw(model_dir, host_cache_gb=-1.0)


def test_auto_budget_resolution(model_dir):
    # Explicit values win; chaos turns auto off but not explicit.
    assert _fw(model_dir, host_cache_gb=0.0).effective_host_cache_bytes() == 0
    assert _fw(model_dir, host_cache_gb=2.0).effective_host_cache_bytes() == int(2e9)
    chaos = FaultConfig(enabled=True, seed=1)
    assert _fw(model_dir, faults=chaos).effective_host_cache_bytes() == 0
    assert (
        _fw(model_dir, host_cache_gb=1.0, faults=chaos).effective_host_cache_bytes()
        == int(1e9)
    )
    auto = _fw(model_dir).effective_host_cache_bytes()
    assert auto >= 0  # fraction of free RAM, or 0 when unknown


def test_explicit_budget_pins_process_cache_against_auto_growth(model_dir):
    # An operator-pinned explicit cap must survive a later auto-config
    # component in the same process (auto only grows auto-sized caches).
    capped = hostcache.cache_for(_fw(model_dir, host_cache_gb=1.0))
    assert capped is not None and capped.budget_bytes == int(1e9)
    again = hostcache.cache_for(_fw(model_dir))  # auto, same process
    if again is not None:  # auto resolves to 0 on unknown-RAM hosts
        assert again is capped
        assert again.budget_bytes == int(1e9)
    # an auto-sized cache, by contrast, is allowed to grow under auto...
    hostcache.reset_process_cache()
    first = hostcache.cache_for(_fw(model_dir))
    if first is not None:
        grown = hostcache.cache_for(_fw(model_dir))
        assert grown is first and grown.budget_bytes >= first.budget_bytes
        # ...until some config pins it explicitly
        pinned = hostcache.cache_for(_fw(model_dir, host_cache_gb=0.5))
        assert pinned is first and pinned.budget_bytes == int(5e8)
        after = hostcache.cache_for(_fw(model_dir))
        assert after is first and after.budget_bytes == int(5e8)


def test_auto_budget_under_shrinking_memavailable(model_dir, monkeypatch):
    """Auto re-resolution under a SHRINKING MemAvailable: an auto-sized
    cache never shrink-churns against its own entries (auto only grows),
    and no auto resolution — however large the host momentarily looks —
    grows past an explicitly pinned cap."""
    avail = {"bytes": int(8e9)}
    monkeypatch.setattr(
        hostcache, "available_host_bytes", lambda: avail["bytes"]
    )
    first = hostcache.cache_for(_fw(model_dir))
    assert first is not None
    start = first.budget_bytes
    assert start == int(8e9 * hostcache.AUTO_FRACTION)
    # The host tightens (the cache's own entries lower MemAvailable):
    # auto must NOT shrink the budget it already granted.
    avail["bytes"] = int(2e9)
    again = hostcache.cache_for(_fw(model_dir))
    assert again is first and again.budget_bytes == start
    # An explicit cap lands; a later huge-looking auto resolution must
    # not grow past it.
    pinned = hostcache.cache_for(_fw(model_dir, host_cache_gb=0.5))
    assert pinned is first and pinned.budget_bytes == int(5e8)
    avail["bytes"] = int(64e9)
    after = hostcache.cache_for(_fw(model_dir))
    assert after is first and after.budget_bytes == int(5e8)


def test_shrink_evicts_lru_first_without_invalidating_live_hits(tmp_path):
    """The brownout shrink path: set_budget down evicts LRU-first (the
    least-recently-HIT entries go first, counted as evictions, never
    invalidations) and the surviving entries keep serving hits."""
    paths = []
    for i in range(3):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(b"x")
        paths.append(str(p))
    cache = HostShardCache(budget_bytes=300)
    segs = [("decoders", {"w": np.zeros(25, np.uint8)})]  # 100 B nominal
    for i, p in enumerate(paths):
        assert cache.put(("k", i), segs, paths=[p], nbytes=100)
    # Touch entry 0: LRU order becomes 1 (oldest), 2, 0 (newest).
    assert cache.get(("k", 0)) is not None
    before_inval = cache.invalidations
    cache.set_budget(150)
    stats = cache.stats()
    assert stats["entries"] == 1 and stats["evictions"] == 2
    assert cache.invalidations == before_inval  # shrink never invalidates
    # The survivor is the most-recently-hit entry, and it still HITS.
    assert cache.get(("k", 0)) is not None
    assert cache.get(("k", 1)) is None and cache.get(("k", 2)) is None
    # Growth back re-admits new entries normally.
    cache.set_budget(300)
    assert cache.put(("k", 9), segs, paths=[paths[1]], nbytes=100)


# ---------------------------------------------------------------------------
# pinned_host copies of the streamed trees (PR 30)
# ---------------------------------------------------------------------------
# The CPU backend lists a ``pinned_host`` memory, so the whole path runs
# here: the loader asks the cache for the copy, the cache's own thread makes
# it, a later hit returns jax.Array leaves in that memory, and _place moves
# them with a memory-space device_put.

import json
import threading
import time

from flexible_llm_sharding_tpu.runtime import executor as executor_mod
from flexible_llm_sharding_tpu.runtime.executor import (
    BroadcastShardSource,
    ShardWeightSource,
    _on_pinned_host,
    _pinned_host_of,
)
from flexible_llm_sharding_tpu.utils.checkpoint import requantize_native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def family_dirs(tmp_path_factory):
    """bfloat16 layer files of the benchmark's two families at their
    rehearsal widths: a ``deepseek_v3`` model (a dense layer, then expert
    layers) and MiMo-V2-Flash ([full+dense, window, window, full])."""
    from benchmark import weights as dsv3
    from benchmark.families.mimo_v2_flash import weights as mimo

    out = {}
    for key, mod, fn in (
        ("moonlight", dsv3, "moonlight-16b-a3b.json"),
        ("mimo", mimo, "mimo-v2-flash.json"),
    ):
        with open(os.path.join(ROOT, "benchmark", "configs", fn)) as f:
            model = json.load(f)
        model.update(model.pop("rehearsal"))
        d = str(tmp_path_factory.mktemp(key) / "model")
        mod.write_model(model, 30, d)
        out[key] = d
    return out


def _family_loader(d, cache, dev, np_dtype="bfloat16"):
    from flexible_llm_sharding_tpu.config import LlamaConfig

    mc = LlamaConfig.from_pretrained(d)
    names = layer_names_for(mc.num_hidden_layers, tie_word_embeddings=False)
    return _HostShardLoader(
        d, names, np_dtype_for(np_dtype), mc.tie_word_embeddings,
        mc.layer_sliding, mc.layer_rope, host_cache=cache,
        pinned_host=_pinned_host_of(dev),
    )


def _bits_equal(a, b) -> None:
    """Two placed segment lists: the same kinds, shardings, dtypes, bytes."""
    assert [k for k, _ in a] == [k for k, _ in b]
    for (_, ga), (_, gb) in zip(a, b):
        la, lb = jax.tree.leaves(ga), jax.tree.leaves(gb)
        assert len(la) == len(lb) and la
        for xa, xb in zip(la, lb):
            assert xa.sharding == xb.sharding and xa.dtype == xb.dtype
            assert np.asarray(xa).tobytes() == np.asarray(xb).tobytes()


@pytest.mark.parametrize(
    "family,idxs,compute",
    [
        ("moonlight", (2,), "bfloat16"),  # an expert layer, the k=1 [None] view
        ("moonlight", (2, 3), "bfloat16"),  # two stacked (np.stack's copy)
        ("moonlight", (2,), "float32"),  # the on-device cast reads the leaves
        ("mimo", (2,), "bfloat16"),  # a window layer (sink, sliding flag)
        ("mimo", (4,), "bfloat16"),  # a full layer of other leaf shapes
        ("mimo", (3, 4), "bfloat16"),  # a run the builder breaks on shape
    ],
)
def test_place_of_a_pinned_host_tree_is_bit_identical(family_dirs, family, idxs, compute):
    dev = jax.devices()[0]
    cache = HostShardCache(budget_bytes=1 << 30)
    loader = _family_loader(family_dirs[family], cache, dev, compute)
    try:
        host = loader.build_host_shard(idxs)  # NumPy; asks for the copy
        assert _on_pinned_host(host) is None
        assert cache.pin_wait()
        pinned = loader.build_host_shard(idxs)  # the hit: the copy
    finally:
        loader.close()
    assert _on_pinned_host(pinned) == dev
    for (_, seg), (_, seg_np) in zip(pinned, host):
        for leaf, leaf_np in zip(jax.tree.leaves(seg), jax.tree.leaves(seg_np)):
            assert leaf.sharding.memory_kind == "pinned_host"
            assert leaf.shape == leaf_np.shape and leaf.dtype == leaf_np.dtype
    s = cache.stats()
    assert (s["pinned_host_copies"], s["pinned_host_bytes"]) == (1, s["bytes"])
    dt = np_dtype_for(compute)
    want = _place(host, dev, np_dtype=dt)
    _bits_equal(_place(pinned, dev, np_dtype=dt), want)
    assert all(
        x.dtype == dt
        for _, seg in want
        for x in jax.tree.leaves(seg)
        if jax.numpy.issubdtype(x.dtype, jax.numpy.floating)
    )


def _run_source(source):
    try:
        for _ in source:
            pass
    finally:
        source.close()


def _all_numpy(cache) -> bool:
    with cache._lock:
        trees = [e[0] for e in cache._entries.values()]
    return bool(trees) and all(_on_pinned_host(t) is None for t in trees)


@pytest.mark.parametrize("holder", ["quantized", "broadcast", "per_shard_devices", "tp_placement"])
def test_other_holders_keep_numpy_trees(model_dir, tmp_path, holder):
    """What holds a tree decides its form: a tree that is dequantized on
    placement, a source that feeds several chips, and a placement keep the
    NumPy tree and ask for no copy."""
    devs = jax.devices()
    names = layer_names_for(4, tie_word_embeddings=False)
    shards = [(i,) for i in range(len(names))]
    cache = HostShardCache(budget_bytes=1 << 30)
    kw = dict(prefetch_depth=2, host_cache=cache)
    if holder == "quantized":
        q8 = str(tmp_path / "q8")
        requantize_native(model_dir, q8, dtype="int8")
        source = ShardWeightSource(
            q8, names, shards, np.dtype(np.float32), device=devs[0], **kw
        )
        assert source._loader._pinned_host is not None  # the tree decides
    elif holder == "broadcast":
        source = BroadcastShardSource(
            model_dir, names, shards, np.dtype(np.float32), devs[:2], **kw
        )
        views = [source.view(r) for r in range(2)]
        threads = [
            threading.Thread(target=_run_source, args=(v,))
            for v in views[1:]
        ]
        for t in threads:
            t.start()
        _run_source(views[0])
        for t in threads:
            t.join()
        assert source._loader._pinned_host is None
    elif holder == "per_shard_devices":
        source = ShardWeightSource(
            model_dir, names, shards, np.dtype(np.float32),
            devices=[devs[i % 2] for i in range(len(shards))], **kw
        )
        assert source._loader._pinned_host is None
    else:
        from flexible_llm_sharding_tpu.config import LlamaConfig
        from flexible_llm_sharding_tpu.parallel.sharding import TpPlacement

        placement = TpPlacement(devs[:2], LlamaConfig.from_pretrained(model_dir))
        source = ShardWeightSource(
            model_dir, names, shards, np.dtype(np.float32), device=placement, **kw
        )
        assert source._loader._pinned_host is None
    if holder != "broadcast":
        _run_source(source)
    else:
        source.close()
    assert cache.pin_wait()
    s = cache.stats()
    assert s["entries"] == len(shards)
    if holder == "quantized":
        # The final norm's scale is stored as it travels and is the one
        # tree of this directory without a quantized leaf.
        with cache._lock:
            trees = [e[0] for e in cache._entries.values()]
        quantized = [
            t for t in trees if any(executor_mod._has_quantized(seg) for _, seg in t)
        ]
        assert len(quantized) == len(shards) - 1
        assert all(_on_pinned_host(t) is None for t in quantized)
        assert s["pinned_host_copies"] == 1
    else:
        assert (s["pinned_host_copies"], s["pinned_host_bytes"]) == (0, 0)
        assert _all_numpy(cache)


def _pinned_entry(model_dir, cache, idxs=(1,)):
    loader = _loader(model_dir, cache=cache, pinned_host=_pinned_host_of(jax.devices()[0]))
    loader.build_host_shard(idxs)
    assert cache.pin_wait()
    tree = loader.build_host_shard(idxs)
    assert _on_pinned_host(tree) is not None
    return loader, tree


@pytest.mark.parametrize("how", ["stat_drift", "invalidate_path", "eviction", "reset_process_cache"])
def test_dropping_an_entry_releases_its_pinned_copy(model_dir, tmp_path, how):
    """The pinned tree lives and dies with its entry: whatever drops the
    entry gives back the budget's bytes and the cache's hold on the copy
    (the arrays are freed once no sweep holds them either)."""
    import gc
    import shutil
    import weakref

    d = str(tmp_path / "model")
    shutil.copytree(model_dir, d)
    if how == "reset_process_cache":
        cache = hostcache.cache_for(_fw(d, host_cache_gb=1.0))
    else:
        cache = HostShardCache(budget_bytes=1 << 30)
    loader, tree = _pinned_entry(d, cache)
    nbytes = cache.stats()["bytes"]
    assert cache.stats()["pinned_host_bytes"] == nbytes > 0
    ref = weakref.ref(jax.tree.leaves(tree[0][1])[0])
    del tree
    path = loader._layer_file(loader.layer_names[1])
    if how == "stat_drift":
        os.utime(path, ns=(1, 1))
        rebuilt = loader.build_host_shard((1,))  # stale: dropped, re-read
        assert _on_pinned_host(rebuilt) is None  # a fresh NumPy tree
        assert cache.stats()["invalidations"] == 1
        cache.clear()  # its new copy is not this test's
        del rebuilt
    elif how == "invalidate_path":
        assert cache.invalidate_path(path) == 1
    elif how == "eviction":
        cache.set_budget(nbytes - 1)
        assert cache.stats()["evictions"] == 1
    else:
        hostcache.reset_process_cache()
    loader.close()
    cache.pin_wait()
    s = cache.stats()
    assert (s["bytes"], s["pinned_host_bytes"], s["entries"]) == (0, 0, 0)
    gc.collect()
    assert ref() is None


def test_pinned_copy_is_charged_like_any_entry_and_refusal_ends_pinning(model_dir, monkeypatch):
    """The copy replaces the entry's tree in place (same key, guard and
    bytes), hits and misses count as before, and a copy the runtime refuses
    leaves the NumPy tree serving and asks no more."""
    cache = HostShardCache(budget_bytes=1 << 30)
    loader, tree = _pinned_entry(model_dir, cache)
    s = cache.stats()
    assert (s["hits"], s["misses"], s["entries"]) == (1, 1, 1)
    assert s["bytes"] == s["pinned_host_bytes"] == sum(
        a.nbytes for _, seg in tree for a in jax.tree.leaves(seg)
    )
    assert s["pinned_host_copy_s"] > 0
    assert cache.pin(loader._cache_key_base + ((1,),), tree, loader._pinned_host) is False

    def refuse(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: no more pinned host memory")

    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", refuse)
    host = loader.build_host_shard((2,))
    assert cache.pin_wait()
    monkeypatch.setattr(jax, "device_put", real_put)
    assert "RESOURCE_EXHAUSTED" in cache.pin_error
    again = loader.build_host_shard((2,))
    assert again is host and _on_pinned_host(again) is None
    loader.build_host_shard((3,))
    assert cache.pin_wait() and cache.stats()["pinned_host_copies"] == 1
    loader.close()


def test_executor_sweeps_upload_from_pinned_host_and_match(model_dir, clean_scores):
    """Sweep 1 builds and uploads NumPy trees and asks for the copies;
    once they are made every streamed byte takes the new path
    (upload_pinned_bytes == upload_bytes) and the scores do not move."""
    dev = jax.devices()[0]
    cfg = _fw(model_dir, host_cache_gb=1.0, prefetch_depth=2)
    for sweep in range(3):
        out = StreamingExecutor(cfg, device=dev, tokenizer=FakeTokenizer())(list(PROMPTS))
        assert hostcache.process_cache().pin_wait()
        rec = executor_mod.process_sweep_log()[-1]
        assert rec["upload_bytes"] > 0
        if sweep:
            assert rec["upload_pinned_bytes"] == rec["upload_bytes"]
        else:
            assert rec["upload_pinned_bytes"] == 0
        for a, b in zip(clean_scores, out):
            np.testing.assert_array_equal(a, b)
    s = hostcache.process_cache().stats()
    assert s["pinned_host_bytes"] == s["bytes"] and s["hit_rate"] == pytest.approx(2 / 3, abs=1e-4)
    # The default device (no stated target) keeps today's uncommitted uploads.
    hostcache.reset_process_cache()
    StreamingExecutor(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    assert hostcache.process_cache().pin_wait()
    assert hostcache.process_cache().stats()["pinned_host_copies"] == 0


def test_pins_puts_and_drops_from_many_threads_keep_the_books(tmp_path):
    """More threads than cores put, pin, hit and drop the same few keys
    under a shortened switch interval: whatever interleaving, the bytes the
    cache charges and the bytes it reports as pinned are those of the
    entries it holds, and its thread retires."""
    import sys

    files = []
    for i in range(4):
        f = tmp_path / f"layer{i}.bin"
        f.write_bytes(b"x" * 64)
        files.append(str(f))
    sharding = _pinned_host_of(jax.devices()[0])
    cache = HostShardCache(budget_bytes=3 * 4096 + 100)  # three of four fit
    stop = time.monotonic() + 3.0
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            while time.monotonic() < stop:
                k = int(rng.integers(4))
                tree = [("decoders", {"w": np.full((1024,), k, np.float32)})]
                op = int(rng.integers(4))
                if op == 0 and cache.put(k, tree, paths=[files[k]]):
                    cache.pin(k, tree, sharding)
                elif op == 1:
                    hit = cache.get(k, sharding)
                    if hit is not None:
                        leaf = jax.tree.leaves(hit[0][0][1])[0]
                        assert float(np.asarray(leaf)[0]) == k
                        cache.pin(k, hit[0], sharding)
                elif op == 2:
                    cache.invalidate_path(files[k])
                else:
                    cache.set_budget(int(rng.choice([2, 3, 4])) * 4096 + 100)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[:1]
    assert cache.pin_wait(30) and cache.pin_error is None
    with cache._lock:
        entries = dict(cache._entries)
        pinned = set(cache._pinned_keys)
        assert not cache._pin_requests and cache._pin_thread is None
    assert cache.bytes == sum(e[1] for e in entries.values()) <= cache.budget_bytes
    assert pinned <= set(entries)
    assert cache.pinned_host_bytes == sum(entries[k][1] for k in pinned)
    for k, e in entries.items():
        assert (_on_pinned_host(e[0]) is not None) == (k in pinned)
    assert cache.stats()["pinned_host_copies"] >= 1


def test_put_without_evict_takes_only_free_room(tmp_path):
    """A tree that is read once (a seat of the residency tier) is cached
    where there is room and pushes nothing out; a later evicting put treats
    it like any entry."""
    f = tmp_path / "layer.bin"
    f.write_bytes(b"x" * 8)
    tree = [("decoders", {"w": np.zeros((25,), np.float32)})]  # 100 bytes
    cache = HostShardCache(budget_bytes=250)
    assert cache.put("a", tree, paths=[str(f)])
    assert cache.put("seat1", tree, paths=[str(f)], evict=False)
    assert not cache.put("seat2", tree, paths=[str(f)], evict=False)
    s = cache.stats()
    assert (s["entries"], s["bytes"], s["evictions"]) == (2, 200, 0)
    assert cache.get("a") is not None and cache.get("seat2") is None
    # A refused put of a key that was held drops the old tree all the same:
    # the caller built a new one because the old one missed.
    assert not cache.put("seat1", [("decoders", {"w": np.zeros((50,), np.float32)})],
                         paths=[str(f)], evict=False)
    assert cache.get("seat1") is None and cache.stats()["bytes"] == 100
    assert cache.put("seat1", tree, paths=[str(f)], evict=False)
    assert cache.put("b", tree, paths=[str(f)])  # evicts the oldest: "a"
    assert cache.get("a") is None and cache.stats()["evictions"] == 1


def test_a_pinned_tree_is_only_for_its_own_target(tmp_path):
    """Keys are chip-free. The copy in one chip's pinned_host memory is
    handed to that chip's readers alone: a reader with another target (a
    second chip, or None for several) drops it and misses, and the key is
    never pinned again until clear(); a request under way when the second
    reader comes is not installed."""
    f = tmp_path / "layer.bin"
    f.write_bytes(b"x" * 8)
    devs = jax.devices()
    on0, on1 = _pinned_host_of(devs[0]), _pinned_host_of(devs[1])
    tree = [("decoders", {"w": np.arange(64, dtype=np.float32)})]
    cache = HostShardCache(budget_bytes=1 << 20)
    assert cache.get("k", on0) is None and cache.put("k", tree, paths=[str(f)])
    assert cache.pin("k", tree, on1) is False  # not this key's reader
    assert cache.pin("k", tree, on0) and cache.pin_wait()
    assert _on_pinned_host(cache.get("k", on0)[0]) == devs[0]
    for other in (on1, None):
        assert cache.get("k", other) is None  # dropped, a miss
        s = cache.stats()
        assert (s["entries"], s["bytes"], s["pinned_host_bytes"]) == (0, 0, 0)
        assert cache.put("k", tree, paths=[str(f)])
        assert cache.pin("k", tree, on0) is False and cache.pin("k", tree, on1) is False
        for reader in (on0, on1, None):
            assert cache.get("k", reader)[0] is tree
        cache.clear()  # forgets the readers too
        assert cache.get("k", on0) is None and cache.put("k", tree, paths=[str(f)])
        assert cache.pin("k", tree, on0) and cache.pin_wait()
    assert cache.stats()["pinned_host_copies"] == 3
    # The second reader comes while the copy is being made.
    cache.clear()
    gate = threading.Event()
    real_put = jax.device_put

    def slow_put(x, s=None, **kw):
        if s is on0:
            assert gate.wait(20)
        return real_put(x, s, **kw)

    assert cache.get("k", on0) is None and cache.put("k", tree, paths=[str(f)])
    jax.device_put = slow_put
    try:
        assert cache.pin("k", tree, on0)
        assert cache.get("k", on1)[0] is tree
        gate.set()
        assert cache.pin_wait()
    finally:
        jax.device_put = real_put
    assert cache.get("k", on0)[0] is tree
    s = cache.stats()
    assert (s["pinned_host_copies"], s["pinned_host_bytes"]) == (3, 0)


@pytest.mark.parametrize("second", ["comes_late", "from_the_start"])
def test_two_chips_over_one_cache_share_one_entry_a_layer(model_dir, second):
    """A fleet's replicas: one engine a chip over the process's one cache
    (serve/fleet.py). Each layer has ONE entry whatever the number of
    chips: the second chip's source rebuilds a layer it finds pinned for
    the first (once), then both hit the same NumPy tree sweep after sweep;
    the cache's bytes are one model's, no copy is made again, and every
    shard lands on its source's own chip."""
    devs = jax.devices()[:2]
    names = layer_names_for(4, tie_word_embeddings=False)
    shards = [(i,) for i in range(len(names))]
    cache = HostShardCache(budget_bytes=1 << 30)

    def sweep(dev):
        source = ShardWeightSource(
            model_dir, names, shards, np.dtype(np.float32), device=dev,
            prefetch_depth=2, host_cache=cache,
        )
        try:
            for _, segs in source:
                for leaf in jax.tree.leaves([seg for _, seg in segs]):
                    assert leaf.devices() == {dev}
                    assert leaf.sharding.memory_kind == "device"
        finally:
            source.close()
        return source

    n = len(shards)
    if second == "comes_late":
        sweep(devs[0])
        assert cache.pin_wait()
        one_model = cache.stats()["bytes"]
        first = sweep(devs[0])
        assert first.upload_pinned_bytes == first.upload_bytes == one_model
        s = cache.stats()
        assert (s["pinned_host_copies"], s["pinned_host_bytes"]) == (n, one_model)
        sweep(devs[1])  # finds n trees pinned for the other chip
        s = cache.stats()
        assert (s["hits"], s["misses"]) == (n, 2 * n)
    else:
        threads = [threading.Thread(target=sweep, args=(d,)) for d in devs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert cache.pin_wait()
        sweep(devs[0]), sweep(devs[1])  # whoever pinned what, it is undone here
        one_model = cache.stats()["bytes"]
    before = cache.stats()
    assert (before["entries"], before["bytes"], before["pinned_host_bytes"]) == (n, one_model, 0)
    sources = []
    for _ in range(2):
        threads = [
            threading.Thread(target=lambda d=d: sources.append(sweep(d))) for d in devs
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert cache.pin_wait()
    s = cache.stats()
    assert s["hits"] - before["hits"] == 4 * n and s["misses"] == before["misses"]
    assert s["pinned_host_copies"] == before["pinned_host_copies"]
    assert (s["entries"], s["bytes"], s["pinned_host_bytes"]) == (n, one_model, 0)
    assert s["evictions"] == 0 and _all_numpy(cache)
    assert all(src.upload_pinned_bytes == 0 and src.upload_bytes == one_model for src in sources)
