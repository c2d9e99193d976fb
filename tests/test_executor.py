"""End-to-end streaming executor: scores from the layer-streaming path must
equal the monolithic forward, across storage backends and shard sizes — the
storage-parametrized scoring test mandated by SURVEY.md §4."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexible_llm_sharding_tpu.config import FrameworkConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import executor as executor_mod
from flexible_llm_sharding_tpu.runtime import orchestration
from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor
from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer, make_blocks
from flexible_llm_sharding_tpu.utils.checkpoint import (
    layer_names_for,
    load_layer,
    save_params,
)

from tests.fake_tokenizer import FakeTokenizer

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome", " might be Lyon")),
    ("Water boils", (" at 100C", " when heated to its boiling point")),
    ("Two plus two equals", (" four", " five", " twenty-two", " fish")),
]


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d), params


def _expected_scores(params, cfg, tok: PromptTokenizer, prompts):
    """Monolithic forward per (prefix, suffix): softmax at the suffix's last
    real token — the invariant the streaming path must reproduce."""
    out = []
    for prefix, suffixes in prompts:
        t = tok(prefix, suffixes)
        rows = []
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            full = np.concatenate(
                [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
            )[None, :]
            logits = llama.forward_full(params, cfg, jnp.asarray(full))
            rows.append(np.asarray(jax.nn.softmax(logits[0, -1])))
        out.append(np.stack(rows)[:, None, :])
    return out


@pytest.fixture(scope="module")
def expected(tiny_cfg, model_dir):
    _, params = model_dir
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    return _expected_scores(params, tiny_cfg, tok, PROMPTS)


@pytest.mark.parametrize("storage", ["tpu", "cpu", "disk"])
def test_executor_matches_monolithic(tiny_cfg, model_dir, expected, storage, tmp_path):
    path, _ = model_dir
    cfg = FrameworkConfig(
        model_path=path,
        layer_num_per_shard=1,
        storage_location=storage,
        disk_folder=str(tmp_path / "acts"),
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex(list(PROMPTS))
    assert len(got) == len(PROMPTS)
    for g, w, (_, sfx) in zip(got, expected, PROMPTS):
        assert g.shape == (len(sfx), 1, tiny_cfg.vocab_size)
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("storage", ["disk", "cpu"])
def test_executor_bfloat16_disk_roundtrip(tiny_cfg, model_dir, storage, tmp_path):
    """bf16 activations must survive the disk .npy roundtrip: ml_dtypes
    extension types serialize as raw void bytes that JAX rejects unless the
    store restores the real dtype (regression: the 7B scale demo crashed at
    shard 1 of a disk-mode bf16 run). cpu mode with max_in_cpu=1 forces the
    spill path through the same files."""
    path, _ = model_dir
    base = dict(
        model_path=path,
        layer_num_per_shard=1,
        disk_folder=str(tmp_path / "acts"),
        dtype="bfloat16",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
    )
    ref = StreamingExecutor(
        FrameworkConfig(storage_location="tpu", **base), tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    cfg = FrameworkConfig(
        storage_location=storage,
        max_activation_in_cpu=1 if storage == "cpu" else 100,
        **base,
    )
    got = StreamingExecutor(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    for g, w in zip(got, ref):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("lnps", [2, 3, 100])
def test_executor_shard_sizes(tiny_cfg, model_dir, expected, lnps):
    path, _ = model_dir
    cfg = FrameworkConfig(
        model_path=path,
        layer_num_per_shard=lnps,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=1,  # exercises the prefetch thread
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex(list(PROMPTS))
    for g, w in zip(got, expected):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def _layer_at_a_time_scores(path, cfg, prompts, decoder_order=None):
    """The reference's schedule, plainly, sharing no code with
    ``runtime/executor.py``: one layer file at a time, one prompt at a time,
    every activation back on the host between two layers. No shards, no
    stacked scan, no blocks, no prefetch, no store. ``decoder_order``
    permutes the decoder layers (the control: scores must then differ)."""
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    toks = [tok(prefix, suffixes) for prefix, suffixes in prompts]
    names = layer_names_for(cfg.num_hidden_layers)
    if decoder_order is not None:
        names = [names[0], *(names[1 + i] for i in decoder_order), *names[-2:]]
    acts = [None] * len(toks)
    scores = [None] * len(toks)
    for name in names:
        params = jax.tree.map(jnp.asarray, load_layer(path, name))
        for i, t in enumerate(toks):
            if name == "model.embed_tokens":
                ph = llama.embed(params, jnp.asarray(t.prefix_ids), jnp.float32, cfg)
                sh = llama.embed(params, jnp.asarray(t.suffix_ids), jnp.float32, cfg)
            else:
                ph, sh = (jnp.asarray(a) for a in acts[i])
                if name.startswith("model.layers."):
                    ph, sh = llama.prefix_suffix_layer(
                        params, cfg, ph, sh, jnp.int32(t.prefix_len)
                    )
                elif name == "model.norm":
                    sh = llama.select_eos_and_norm(
                        params, cfg, sh, jnp.asarray(t.suffix_eos)
                    )
                else:  # lm_head
                    sc = llama.lm_head_scores(params, sh, cfg.final_logit_softcap)
                    scores[i] = np.asarray(sc)[: t.num_suffixes, None, :]
            acts[i] = (np.asarray(ph), np.asarray(sh))
    return scores


@pytest.mark.parametrize("decoder_order", [None, (0, 2, 1, 3)], ids=["in_order", "swapped"])
def test_reference_schedule_matches_executor(tiny_cfg, model_dir, decoder_order):
    """The overlapped executor (shards of two stacked layers under one scan,
    blocks of prompts, a prefetch thread, the cpu activation store) against
    the plain layer-at-a-time schedule on the same files: the same scores.
    With two layers of one shard swapped in the plain schedule the scores
    must differ, so a shard applied out of order cannot pass the first case."""
    path, _ = model_dir
    cfg = FrameworkConfig(
        model_path=path,
        layer_num_per_shard=2,
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=2,
    )
    got = StreamingExecutor(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    want = _layer_at_a_time_scores(path, tiny_cfg, PROMPTS, decoder_order)
    assert len(got) == len(want) == len(PROMPTS)
    for g, w in zip(got, want):
        assert np.asarray(g).shape == w.shape
    close = [
        np.allclose(np.asarray(g, np.float32), w, rtol=1e-5, atol=1e-6)
        for g, w in zip(got, want)
    ]
    if decoder_order is None:
        assert all(close)
    else:
        assert not any(close)


def test_executor_tied_embeddings(tiny_cfg, tmp_path):
    """Tied-embedding checkpoints (no lm_head file, Llama-3.2 style): the
    head kernel is re-materialised from the embedding at stream time."""
    import dataclasses

    cfg_tied = dataclasses.replace(tiny_cfg, tie_word_embeddings=True)
    params = llama.init_params(jax.random.PRNGKey(7), cfg_tied)
    assert "lm_head" not in params
    d = tmp_path / "tied_model"
    save_params(jax.tree.map(np.asarray, params), str(d), cfg_tied)
    assert not (d / "lm_head.safetensors").exists()

    cfg = FrameworkConfig(
        model_path=str(d),
        storage_location="cpu",
        dtype="float32",
        bucket_multiple=8,
        prefetch_depth=0,
    )
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    got = ex(PROMPTS[:1])

    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    t = tok(*PROMPTS[0])
    full = np.concatenate(
        [t.prefix_ids[: t.prefix_len], t.suffix_ids[0, : int(t.suffix_eos[0]) + 1]]
    )[None, :]
    logits = llama.forward_full(params, cfg_tied, jnp.asarray(full))
    want = np.asarray(jax.nn.softmax(logits[0, -1]))
    np.testing.assert_allclose(got[0][0, 0], want, rtol=1e-4, atol=1e-5)


def test_tokenization_bucketing():
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8, suffix_count_multiple=4)
    t = tok("hello world", ("a", "bc", "def"))
    lp, s, ls = t.prefix_ids.shape[0], *t.suffix_ids.shape
    assert lp % 8 == 0 and ls % 8 == 0 and s == 4
    assert t.num_suffixes == 3
    # BOS stripped from suffixes, kept on prefix.
    assert t.prefix_ids[0] == FakeTokenizer.BOS
    assert (t.suffix_ids[:3, 0] != FakeTokenizer.BOS).all()
    # suffix_eos = last real token, zero-based (ref utils.py:258).
    assert list(t.suffix_eos[:3]) == [0, 1, 2]
    # padding rows are all pad.
    assert (t.suffix_ids[3] == tok.pad_id).all()


def test_make_blocks_groups_by_bucket():
    tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
    toks = [tok(p, s) for p, s in PROMPTS] * 2
    blocks = make_blocks(toks, block_size=2)
    seen = sorted(i for b in blocks for i in b)
    assert seen == list(range(len(toks)))
    for b in blocks:
        assert len(b) <= 2
        keys = {toks[i].bucket_key for i in b}
        assert len(keys) == 1


# ---------------------------------------------------------------------------
# The per-sweep account (process_sweep_log) and the upload completion thread
# ---------------------------------------------------------------------------

PHASES = ("head_s", "source_wait_s", "dispatch_s", "device_wait_s", "tail_s")
# The consumer's spans on the timeline, and the phases each one's time is.
SPAN_OF_PHASE = {
    "sweep_head": ("head_s",),
    "source_wait": ("source_wait_s",),
    "compute": ("dispatch_s", "device_wait_s"),
    "sweep_tail": ("tail_s",),
}


def _assert_phases_partition(rec, slack_s=0.02):
    """The consumer's five phases are disjoint stretches of the sweep's
    wall: none counted twice (their sum cannot exceed ``wall_s``) and none
    missing (what no phase accounts for is the stamps between two spans:
    microseconds, a thread switch under a loaded machine at most, so an
    absolute bound and not a share of a toy sweep's tens of milliseconds)."""
    assert all(rec[k] >= 0.0 for k in PHASES)
    total = sum(rec[k] for k in PHASES)
    assert total <= rec["wall_s"] + 1e-9
    assert rec["wall_s"] - total < slack_s


def _account_cfg(path, **kw):
    base = dict(
        model_path=path, layer_num_per_shard=1, storage_location="cpu",
        dtype="float32", bucket_multiple=8, block_size=2, prefetch_depth=2,
    )
    base.update(kw)
    return FrameworkConfig(**base)


def ONE_CHIP():
    """The suite runs on 8 virtual devices, where run_prompts would take
    its pipeline path: the account is the single-executor path's."""
    return jax.devices()[:1]


def _watchers():
    return [t for t in threading.enumerate() if t.name == "fls-upload-watch"]


@pytest.mark.parametrize("prefetch_depth", [0, 2])
def test_run_prompts_writes_one_sweep_record(model_dir, prefetch_depth):
    path, _ = model_dir
    before = executor_mod.process_sweep_log()
    bytes0 = executor_mod.process_streamed_bytes()
    t0 = time.perf_counter()
    orchestration.run_prompts(
        _account_cfg(path, prefetch_depth=prefetch_depth), list(PROMPTS),
        tokenizer=FakeTokenizer(), devices=ONE_CHIP(),
    )
    outside = time.perf_counter() - t0
    log = executor_mod.process_sweep_log()
    new = [r for r in log if r["sweep_id"] not in {b["sweep_id"] for b in before}]
    (rec,) = new  # one pass over the shards, one record
    # The consumer's five phases partition the wall (entry of run_prompts ->
    # scores returned), which the same call timed from outside contains.
    _assert_phases_partition(rec)
    assert 0.9 * outside <= rec["wall_s"] <= outside
    # The link's side: bytes by construction the streamed-bytes counter's
    # delta, one upload per shard, all seen to completion, busy <= wall.
    assert rec["upload_bytes"] == executor_mod.process_streamed_bytes() - bytes0 > 0
    assert rec["uploads"] == 4 + 3 and rec["upload_misses"] == 0  # layers + embed/norm/head
    assert 0.0 < rec["upload_busy_s"] <= rec["wall_s"]
    assert rec["upload_dispatch_s"] > 0.0 and rec["host_build_s"] > 0.0
    assert rec["producer_blocked_s"] >= 0.0
    # cpu storage: activations cross the link twice a shard.
    assert rec["act_bytes"] > 0 and rec["act_fetch_s"] > 0 and rec["act_store_s"] > 0
    # The store's waits for the device are device_wait_s, not the host's
    # dispatch_s: what is left of its round trips is host work.
    assert 0.0 < rec["act_wait_s"] <= rec["device_wait_s"]
    assert rec["act_wait_s"] <= rec["act_fetch_s"] + rec["act_store_s"]
    assert (
        rec["act_fetch_s"] + rec["act_store_s"] - rec["act_wait_s"]
        <= rec["dispatch_s"]
    )
    assert not _watchers()  # the completion thread never outlives close()


def test_num_batch_passes_each_write_a_record_and_first_owns_the_head(model_dir):
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    path, _ = model_dir
    n0 = len(executor_mod.process_sweep_log())
    tracer = obs_trace.TRACER
    tracer.clear()
    tracer.enable()
    try:
        orchestration.run_prompts(
            _account_cfg(path, num_batch=2), list(PROMPTS),
            tokenizer=FakeTokenizer(), devices=ONE_CHIP(),
        )
        spans = tracer.snapshot()
    finally:
        tracer.disable()
        tracer.clear()
    first, second = executor_mod.process_sweep_log()[n0:][-2:]
    assert second["sweep_id"] > first["sweep_id"]
    for rec in (first, second):
        _assert_phases_partition(rec)
        # The same stamps twice: each phase of the record is the sum of its
        # spans on the timeline (the ring rounds to the microsecond), and
        # those spans lie inside the sweep's own, one after another: exact
        # whatever the machine's load.
        mine = sorted(
            (s for s in spans if s.get("sweep_id") == rec["sweep_id"]),
            key=lambda s: s["ts_s"],
        )
        (sweep,) = [s for s in mine if s["name"] == "sweep"]
        parts = [s for s in mine if s["name"] in SPAN_OF_PHASE]
        for name, keys in SPAN_OF_PHASE.items():
            assert sum(s["dur_s"] for s in parts if s["name"] == name) == (
                pytest.approx(sum(rec[k] for k in keys), abs=1e-5)
            ), name
        assert sweep["dur_s"] == pytest.approx(rec["wall_s"], abs=1e-5)
        ends = [sweep["ts_s"]] + [s["ts_s"] + s["dur_s"] for s in parts]
        for end, nxt in zip(ends, parts):
            assert end <= nxt["ts_s"] + 2e-6, (nxt["name"], end)
        assert ends[-1] <= sweep["ts_s"] + sweep["dur_s"] + 2e-6
    # The executor's construction happens once, inside the first pass's head.
    (init,) = [s for s in spans if s["name"] == "executor_init"]
    assert init["sweep_id"] == first["sweep_id"]
    heads = {s["sweep_id"]: s for s in spans if s["name"] == "sweep_head"}
    assert set(heads) == {first["sweep_id"], second["sweep_id"]}
    assert heads[first["sweep_id"]]["ts_s"] <= init["ts_s"]


def test_stats_keep_their_keys_from_the_same_stamps(model_dir):
    path, _ = model_dir
    ex = StreamingExecutor(_account_cfg(path), tokenizer=FakeTokenizer())
    ex(list(PROMPTS))
    rec = executor_mod.process_sweep_log()[-1]
    for key in ("compute_wall_s", "source_wait_s", "produce_wall_s",
                "total_wall_s", "streamed_bytes", "load_weights_time_s"):
        assert key in ex.stats, key
    assert ex.stats["source_wait_s"] == rec["source_wait_s"]
    assert ex.stats["compute_wall_s"] == pytest.approx(
        rec["dispatch_s"] + rec["device_wait_s"]
    )
    assert ex.stats["streamed_bytes"] == rec["upload_bytes"]
    assert ex.stats["total_wall_s"] <= rec["wall_s"]


def test_aborted_sweep_leaves_no_thread_no_array_and_no_record(model_dir, monkeypatch):
    path, _ = model_dir
    made = []
    orig_init = executor_mod.ShardWeightSource.__init__

    def spy(self, *a, **k):
        orig_init(self, *a, **k)
        made.append(self)

    monkeypatch.setattr(executor_mod.ShardWeightSource, "__init__", spy)

    class Boom(RuntimeError):
        pass

    calls = {"n": 0}
    orig_block = executor_mod.process_block

    def exploding(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise Boom()
        return orig_block(*a, **k)

    monkeypatch.setattr(executor_mod, "process_block", exploding)
    n0 = len(executor_mod.process_sweep_log())
    with pytest.raises(Boom):
        orchestration.run_prompts(
            _account_cfg(path), list(PROMPTS), tokenizer=FakeTokenizer(),
            devices=ONE_CHIP(),
        )
    (source,) = made
    assert not _watchers() and not source._watcher._thread.is_alive()
    assert source._watcher._q.empty()  # nothing handed over is still held
    source._watcher.watch([jnp.ones(3)], 0.0, {})  # closed: holds nothing
    assert source._watcher._q.empty()
    assert len(executor_mod.process_sweep_log()) == n0  # no half record
    # The thread that aborted can open the next sweep's clock cleanly.
    monkeypatch.setattr(executor_mod, "process_block", orig_block)
    orchestration.run_prompts(
        _account_cfg(path), list(PROMPTS), tokenizer=FakeTokenizer(),
        devices=ONE_CHIP(),
    )
    assert len(executor_mod.process_sweep_log()) == n0 + 1


def test_deleted_array_is_a_counted_miss_not_an_error():
    w = executor_mod._UploadWatcher()
    try:
        gone = jnp.ones((4,))
        gone.delete()
        w.watch([gone], time.perf_counter(), {"sweep_id": 0})
        w.watch([jnp.ones((4,))], time.perf_counter(), {"sweep_id": 0})
    finally:
        w.close()
    intervals, misses = w.snapshot()
    assert (len(intervals), misses) == (1, 1)
    assert not w._thread.is_alive()


def test_cycling_source_runs_no_completion_thread(model_dir):
    """The serve engine's source lives as long as the engine and no account
    reads it: it gets no watcher, so nothing grows with the sweeps and its
    close() (the watchdog's recovery path) joins one thread, not two."""
    path, _ = model_dir
    ex = StreamingExecutor(_account_cfg(path), tokenizer=FakeTokenizer())
    source = executor_mod.ShardWeightSource(
        path, ex.layer_names, ex.plan.shards, ex._np_dtype,
        device=jax.devices()[0], prefetch_depth=2, cycle=True,
    )
    try:
        it = iter(source)
        for _ in range(5 * len(ex.plan.shards)):  # five sweeps
            next(it)
            assert source._watcher is None and not _watchers()
        acct = source.account(0.0, time.perf_counter())
        assert acct["uploads"] == 0 and acct["upload_busy_s"] == 0.0
        assert acct["upload_bytes"] > 0  # the bytes' counter needs no thread
    finally:
        source.close()
    assert not _watchers()


def test_completion_thread_keeps_bounded_state():
    from collections import deque

    w = executor_mod._UploadWatcher()
    assert w.intervals.maxlen is not None
    w.intervals = deque(maxlen=8)
    try:
        for _ in range(40):
            w.watch([jnp.ones((2,))], time.perf_counter(), {"sweep_id": 0})
    finally:
        w.close()
    intervals, misses = w.snapshot()
    assert (len(intervals), misses) == (8, 0)
    assert w._q.empty()
    w.close()  # idempotent, and an emptied queue is no error


# ---------------------------------------------------------------------------
# Why the device stood idle, shard by shard; the collector; a slow sweep
# ---------------------------------------------------------------------------

IDLE = ("drained_s", "own_upload_wait_s", "behind_upload_s")
NEW_FIELDS = IDLE + ("drained_shards", "launches_behind_upload", "gc_s",
                     "gc_collections", "slow", "uploads_ordered", "link_wait_s",
                     "waits_deferred")


@pytest.mark.parametrize(
    "case, shards, uploads, want",
    [
        # Launched after its own upload's arrival and before a later
        # shard's (enqueued before the launch): the overlap, to that
        # upload's end, is behind_upload_s; own_upload_wait_s 0.
        ("behind a later shard's upload",
         [(3, 10.0, 10.02, 10.5)], {3: (8.0, 9.0), 6: (9.9, 10.2)},
         [(0.0, 0.0, 0.2)]),
        # Launched before its own upload had arrived: the reverse.
        ("waiting for its own weights",
         [(3, 10.0, 10.02, 10.5)], {3: (9.8, 10.3)},
         [(0.0, 0.3, 0.0)]),
        # Both: the own upload first, then the one enqueued behind it.
        ("its own, then another's",
         [(3, 10.0, 10.02, 10.5)], {3: (9.8, 10.1), 4: (9.9, 10.25)},
         [(0.0, 0.1, 0.15)]),
        # Enqueued between the shard's first and last launch: the later
        # blocks queue behind it, from the enqueue on.
        ("enqueued between two of its launches",
         [(3, 10.0, 10.05, 10.5)], {3: (8.0, 9.0), 6: (10.01, 10.3)},
         [(0.0, 0.0, 0.29)]),
        # The same with the blocks' rows known (1 : 3): the block dispatched
        # before the enqueue still runs, for a third of the 0.2 s the later
        # one takes after the arrival; the idle starts where it ends.
        ("enqueued between two launches, the early block's time taken off",
         [(3, 10.0, 10.05, 10.5)], {3: (8.0, 9.0), 6: (10.01, 10.3)},
         [(0.0, 0.0, round(10.3 - (10.0 + 0.2 / 3), 9))]),
        # An upload enqueued AFTER the last launch holds nothing back, nor
        # does one that arrives after the shard was done (the shard did not
        # wait for it); the own upload's wait ends with the shard's.
        ("enqueued after the last launch, or arrived after the shard's end",
         [(3, 10.0, 10.02, 10.5), (4, 10.6, 10.62, 10.7)],
         {4: (10.05, 11.0), 5: (10.55, 12.0)},
         [(0.0, 0.0, 0.0), (0.1, 0.1, 0.0)]),
        # A shard served from the residency tier has no upload: 0 in both,
        # whatever the link does later.
        ("a pinned shard",
         [(0, 1.0, 1.01, 1.2), (1, 1.25, 1.26, 1.4)], {7: (1.3, 1.5)},
         [(0.0, 0.0, 0.0), (0.05, 0.0, 0.0)]),
        # drained_s is the sum of the boundaries; a shard with no shard-end
        # wait (t_ready None) is accounted with the next one, the sweep's
        # last up to the sweep's end (20.0), and starts no boundary.
        ("boundaries, and a shard without a shard-end wait",
         [(0, 1.0, 1.1, 2.0), (1, 2.25, 2.3, None), (2, 3.0, 3.1, 3.5),
          (3, 4.0, 4.1, None)],
         {1: (2.0, 3.25), 3: (3.9, 25.0)},
         [(0.0, 0.0, 0.0), (0.25, 1.0, 0.0), (0.0, 0.0, 0.25),
          (0.5, 16.0, 0.0)]),
        # A shard that launched nothing (no block) reads zeros.
        ("nothing launched", [(0, None, 4.0, None), (1, 5.0, 5.1, 5.5)], {},
         [(0.0, 0.0, 0.0), (0.0, 0.0, 0.0)]),
        # A many-leaf upload: its device_put call runs from 10.01 to 10.06,
        # leaf by leaf. Dated by the call's START every block after the
        # first would count as queued behind it (0.29 s, the case above);
        # dated by its END (what shard_table passes) the shard's last
        # launch, 10.05, comes before the enqueue and nothing waited.
        ("a many-leaf upload, dated where its call returned",
         [(3, 10.0, 10.05, 10.5)], {3: (8.0, 9.0), 6: (10.06, 10.3)},
         [(0.0, 0.0, 0.0)]),
        # The same call ending between the second launch and the last of
        # four (rows 1 : 1 : 1 : 1, launches at 10.0, 10.02, 10.04, 10.06):
        # the two blocks launched by 10.03 run, half of the 0.2 s the two
        # later ones take after the arrival; the idle starts where they end.
        ("a many-leaf upload ending between two launches",
         [(3, 10.0, 10.06, 10.5)], {3: (8.0, 9.0), 6: (10.03, 10.3)},
         [(0.0, 0.0, round(10.3 - (10.0 + 0.2), 9))]),
        # A wait that lagged: shard 0's end is waited for from 1.12 to 1.30,
        # inside shard 1, whose first block was launched at 1.10, before
        # that wait returned: the boundary did not drain. Shard 1's own wait
        # returns at 1.40 and shard 2 launches at 1.45.
        ("a wait that lagged into the next shard",
         [(0, 1.0, 1.05, 1.30), (1, 1.10, 1.32, 1.40), (2, 1.45, 1.5, 1.6)],
         {}, [(0.0, 0.0, 0.0), (0.0, 0.0, 0.0), (0.05, 0.0, 0.0)]),
    ],
)
def test_idle_split_on_synthetic_stamps(case, shards, uploads, want):
    from flexible_llm_sharding_tpu.utils.intervals import idle_split

    rows = (1, 3) if "rows known" in case or "taken off" in case else ()
    if case == "a many-leaf upload ending between two launches":
        rows = (1, 1, 1, 1)
    got = idle_split(shards, uploads, t_end=20.0, block_rows=rows)
    assert [tuple(round(x, 9) for x in row) for row in got] == want, case


def test_streamed_sweep_accounts_for_the_idle_between_shards(model_dir):
    path, _ = model_dir
    n0 = len(executor_mod.process_sweep_log())
    orchestration.run_prompts(
        _account_cfg(path), list(PROMPTS), tokenizer=FakeTokenizer(),
        devices=ONE_CHIP(),
    )
    (rec,) = executor_mod.process_sweep_log()[n0:]
    _assert_phases_partition(rec)  # the five phases still partition the wall
    assert all(rec[k] >= 0.0 for k in IDLE)
    # Disjoint stretches of the consumer's timeline: never more than it.
    assert sum(rec[k] for k in IDLE) <= rec["wall_s"]
    # 7 shards: every one but the last (the head stores nothing) ends on a
    # wait for the device, so six boundaries are counted.
    assert rec["drained_shards"] == 6 and rec["drained_s"] > 0.0
    assert 0 <= rec["launches_behind_upload"] <= 7
    assert rec["slow"] == 0 and rec["gc_s"] >= 0.0


def test_a_broadcast_sources_record_omits_the_idle_fields(model_dir):
    path, _ = model_dir
    n0 = len(executor_mod.process_sweep_log())
    orchestration.run_prompts(
        _account_cfg(path, data_parallel=True), list(PROMPTS),
        tokenizer=FakeTokenizer(), devices=jax.devices()[:2],
    )
    recs = executor_mod.process_sweep_log()[n0:]
    assert len(recs) == 2  # one a rank
    for rec in recs:
        _assert_phases_partition(rec, slack_s=0.05)
        assert "uploads" not in rec  # the shared source keeps no account
        for key in IDLE + ("drained_shards", "launches_behind_upload"):
            assert key not in rec, key  # omitted, not written as zeros
        assert rec["slow"] == 0 and rec["gc_collections"] >= 0


def test_a_planted_collection_shows_in_the_sweeps_record(model_dir, monkeypatch):
    import gc

    path, _ = model_dir
    orig_block = executor_mod.process_block
    planted = {"n": 0}

    def collecting(*a, **k):
        if not planted["n"]:
            planted["n"] = 1
            gc.collect()  # a full (generation-2) collection
        return orig_block(*a, **k)

    monkeypatch.setattr(executor_mod, "process_block", collecting)
    orchestration.run_prompts(
        _account_cfg(path), list(PROMPTS), tokenizer=FakeTokenizer(),
        devices=ONE_CHIP(),
    )
    rec = executor_mod.process_sweep_log()[-1]
    assert rec["gc_collections"] >= 1 and rec["gc_s"] > 0.0
    assert gc.callbacks.count(executor_mod._gc_hook) == 1  # once a process


def test_a_stalled_sweep_keeps_its_timeline(model_dir, monkeypatch, caplog):
    """Six sweeps of one plan; in the sixth the load of shard 3 sleeps. It
    alone is marked slow, its per-shard table lands in
    process_slow_sweeps() with that shard and the consumer's wait for it
    named, the counter moves by one, and one warning says so."""
    from flexible_llm_sharding_tpu.obs.registry import REGISTRY

    path, _ = model_dir
    with executor_mod._SWEEP_LOG_LOCK:
        executor_mod._SWEEP_LOG.clear()  # peers are this test's own sweeps
    seen0 = REGISTRY.collect()["stream"]["slow_sweeps"]
    kept0 = len(executor_mod.process_slow_sweeps())

    def sweep():
        orchestration.run_prompts(
            _account_cfg(path, host_cache_gb=0), list(PROMPTS),
            tokenizer=FakeTokenizer(), devices=ONE_CHIP(),
        )
        return executor_mod.process_sweep_log()[-1]

    before = [sweep() for _ in range(5)]
    assert [r["slow"] for r in before] == [0] * 5
    assert len(executor_mod.process_slow_sweeps()) == kept0

    orig = executor_mod._HostShardLoader._build_host_shard

    def sleepy(self, layer_idxs, *a, **k):
        if self.trace_ids.get("shard_idx") == 3:
            time.sleep(1.0)
        return orig(self, layer_idxs, *a, **k)

    monkeypatch.setattr(executor_mod._HostShardLoader, "_build_host_shard", sleepy)
    with caplog.at_level("WARNING", logger=executor_mod.__name__):
        rec = sweep()
    assert rec["slow"] == 1
    assert REGISTRY.collect()["stream"]["slow_sweeps"] == seen0 + 1
    assert "fls_stream_slow_sweeps " in REGISTRY.prometheus_text()
    slow = executor_mod.process_slow_sweeps()
    assert len(slow) == kept0 + 1
    kept = slow[-1]
    assert kept["sweep_id"] == rec["sweep_id"] and kept["slow"] == 1
    assert kept["worst_phase"] == "source_wait_s" and kept["worst_shard"] == 3
    assert kept["worst_phase_excess_s"] > 0.9
    assert [r["shard_idx"] for r in kept["shards"]] == list(range(7))
    (row,) = [r for r in kept["shards"] if r["shard_idx"] == 3]
    assert row["source_wait_s"] > 0.9 and row["shard_load_s"] > 0.9
    assert set(row) == {
        "shard_idx", "source_wait_s", "dispatch_s", "device_wait_s",
        "drained_s", "own_upload_wait_s", "behind_upload_s", "shard_load_s",
        "upload_dispatch_s", "upload_ordered",
    }
    warnings = [r for r in caplog.records if "slow sweep" in r.getMessage()]
    assert len(warnings) == 1
    assert "source_wait_s" in warnings[0].getMessage()
    assert "shard 3" in warnings[0].getMessage()
    # The log stays flat dictionaries: no table rides a record.
    for r in executor_mod.process_sweep_log():
        assert not any(isinstance(v, (list, dict)) for v in r.values())


@pytest.mark.parametrize("field", NEW_FIELDS)
def test_each_new_record_field_has_its_help_line(field):
    text = executor_mod.SWEEP_RECORD_HELP[field]
    assert len(text) > 20 and "\n" not in text


def test_store_waits_for_the_device_are_device_wait_spans(model_dir):
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    path, _ = model_dir
    tracer = obs_trace.TRACER
    tracer.clear()
    tracer.enable()
    try:
        orchestration.run_prompts(
            _account_cfg(path), list(PROMPTS), tokenizer=FakeTokenizer(),
            devices=ONE_CHIP(),
        )
        spans = tracer.snapshot()
    finally:
        tracer.disable()
        tracer.clear()
    rec = executor_mod.process_sweep_log()[-1]
    waits = [s for s in spans if s["name"] == "device_wait"]
    at_store = [s for s in waits if s["at"] == "act_store"]
    at_end = [s for s in waits if s["at"] == "shard_end"]
    assert at_store and at_end and len(at_store) + len(at_end) == len(waits)
    assert {s["sweep_id"] for s in waits} == {rec["sweep_id"]}
    assert all(s["shard_idx"] >= 0 for s in waits)
    assert sum(s["dur_s"] for s in at_store) == pytest.approx(
        rec["act_wait_s"], abs=1e-4
    )
    assert sum(s["dur_s"] for s in waits) == pytest.approx(
        rec["device_wait_s"], abs=1e-4
    )
    # Each lies inside one of the store's spans, which lie inside dispatch.
    stores = [s for s in spans if s["name"] in ("act_store", "act_fetch")]
    for w in at_store:
        assert any(
            o["ts_s"] <= w["ts_s"] + 1e-6
            and w["ts_s"] + w["dur_s"] <= o["ts_s"] + o["dur_s"] + 1e-6
            for o in stores
        )


def test_sweep_log_is_bounded():
    for _ in range(300):
        executor_mod.SweepClock().finish(None)
    log = executor_mod.process_sweep_log()
    assert len(log) == 256
    assert [r["sweep_id"] for r in log] == sorted(r["sweep_id"] for r in log)


def test_union_of_upload_intervals_is_clipped_to_the_sweep():
    from flexible_llm_sharding_tpu.utils.intervals import union_seconds as u

    assert u([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)], 0.0, 10.0) == pytest.approx(3.0)
    assert u([(-1.0, 1.0), (3.0, 9.0)], 0.0, 4.0) == pytest.approx(2.0)
    assert u([], 0.0, 1.0) == 0.0


def _deepseek_layer_shapes(cfg):
    """One deepseek_v3 expert layer's parameter shapes: init_params gives
    the Mixtral layout; the router's bias and the shared expert make it the
    DeepSeek one (utils/checkpoint.py's conversion adds the same keys)."""
    layer = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
    )["layers"][1]
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts
    sds = jax.ShapeDtypeStruct
    layer["mlp"] |= {
        "correction_bias": sds((e,), jnp.float32),
        "shared_gate": sds((d, f), jnp.float32),
        "shared_up": sds((d, f), jnp.float32),
        "shared_down": sds((f, d), jnp.float32),
    }
    return layer


def test_lowered_decoder_block_carries_the_scopes_and_kernel_names():
    from flexible_llm_sharding_tpu.config import LlamaConfig

    cfg = LlamaConfig.from_hf_config(dict(
        model_type="deepseek_v3", vocab_size=300, hidden_size=64,
        intermediate_size=48, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=2, num_key_value_heads=2, q_lora_rank=None,
        kv_lora_rank=32, qk_nope_head_dim=48, qk_rope_head_dim=16,
        v_head_dim=64, n_routed_experts=4, num_experts_per_tok=2, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.5,
        n_shared_experts=1, first_k_dense_replace=1, rope_theta=10000.0,
        max_position_embeddings=4096,
    ))
    sds = jax.ShapeDtypeStruct
    seg = {
        "layers": jax.tree.map(
            lambda x: sds((1, *x.shape), x.dtype), _deepseek_layer_shapes(cfg)
        ),
        "sliding": None, "rope": None,
    }
    text = executor_mod._decoder_block.lower(
        cfg, seg, sds((2, 64, 64), jnp.float32), sds((2, 2, 64, 64), jnp.float32),
        sds((2,), jnp.int32), True,  # use_pallas: the kernels (interpreted here)
    ).as_text(debug_info=True)
    for scope in ("decoder_layer", "mla_qkv", "attention", "moe_router",
                  "moe_experts", "moe_shared_experts", "rms_norm",
                  "flash_causal_attention", "flash_prefix_shared_attention"):
        assert scope in text, scope
    # The experts' matmuls sit under their scope inside the layer's, outside
    # the per-prompt vmap (the MLP half sees the block's rows at once).
    assert "decoder_layer/moe_experts" in text
    assert "decoder_layer/vmap(attention)" in text


# ---------------------------------------------------------------------------
# The producer's bound and the seating sweep's pass ahead beside a residency tier (PR 30)
# ---------------------------------------------------------------------------

class _Buffer:
    """Stands for one streamed shard's arrays on the chip: counted while
    anything holds it."""

    alive = 0
    made = 0
    _lock = threading.Lock()

    def __init__(self):
        with _Buffer._lock:
            _Buffer.alive += 1
            _Buffer.made += 1

    def __del__(self):
        with _Buffer._lock:
            _Buffer.alive -= 1


class _SeatedTier:
    """A residency tier whose planned layers are all resident already: such
    a layer's part is ("pin", idx, None), merged from here, never uploaded."""

    def __init__(self, pinned):
        self.pinned = frozenset(pinned)

    def frozen_pinned(self, shards):
        return self.pinned

    def seat_state(self, idx, devices):
        return "seated"

    def seated(self, idx, device):
        return [("decoders", {"resident": idx})]

    def note_skip(self, idx):
        pass

    def max_pinned_device_bytes(self):
        return 0


@pytest.fixture(scope="module")
def deep_dir(tiny_cfg, tmp_path_factory):
    import dataclasses

    cfg = dataclasses.replace(tiny_cfg, num_hidden_layers=8)
    d = tmp_path_factory.mktemp("deep_model")
    save_params(
        jax.tree.map(np.asarray, llama.init_params(jax.random.PRNGKey(1), cfg)),
        str(d), cfg,
    )
    return str(d), layer_names_for(8, tie_word_embeddings=False)


def _tracked_source(monkeypatch, deep_dir, depth, pinned, cycle=False, tier=None, **kw):
    path, names = deep_dir
    _Buffer.alive = _Buffer.made = 0
    monkeypatch.setattr(
        executor_mod, "_place", lambda host, device, np_dtype=None: [("decoders", _Buffer())]
    )
    # The completion thread would hold a shard's arrays for its wait.
    monkeypatch.setattr(executor_mod._UploadWatcher, "watch", lambda self, *a: None)
    return executor_mod.ShardWeightSource(
        path, names, [(i,) for i in range(len(names))], np.dtype(np.float32),
        device=jax.devices()[0], prefetch_depth=depth, cycle=cycle,
        residency=tier or (_SeatedTier(pinned) if pinned else None), **kw,
    )


def _wait_for(cond, timeout_s=20.0):
    t_end = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.005)


# embed, layers 0-2 and the last two files resident; layers 3-7 streamed
_RESIDENT = (0, 1, 2, 3, 9, 10)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_producer_holds_depth_plus_two_streamed_shards(monkeypatch, deep_dir, depth):
    """The slots count shards, resident ones too: with the consumer at the
    head no streamed layer is on its way yet; depth + 1 shards built and not
    dispatched (the queue's places and the one in the producer's hand) and
    the one whose steps are running make at most depth + 2 streamed shards
    alive (what residency.in_flight_bytes reserves), that bound is reached,
    and shards arrive in order."""
    source = _tracked_source(monkeypatch, deep_dir, depth, _RESIDENT)
    try:
        _wait_for(lambda: source._q.full())
        time.sleep(0.05)
        if depth < 3:  # in hand: a resident shard ((2,) or (3,)), not (4,)
            assert _Buffer.made == 0
        got, peak = [], 0
        for idxs, segs in source:
            got.append(idxs)
            source.dispatched()
            time.sleep(0.01)  # let the producer use the slot that returned
            peak = max(peak, _Buffer.alive)
            assert _Buffer.alive <= depth + 2
        del segs
    finally:
        source.close()
    assert got == source.shards
    assert peak == min(depth + 2, 5) and _Buffer.made == 5
    acct = source.account(0.0, time.perf_counter())
    assert acct["pin_hits"] == len(_RESIDENT) and acct["producer_blocked_s"] > 0
    assert _Buffer.alive == 0


@pytest.mark.parametrize("how", ["close", "abort_then_close"])
def test_stopping_a_source_mid_sweep_strands_no_buffer(monkeypatch, deep_dir, how):
    source = _tracked_source(monkeypatch, deep_dir, 2, _RESIDENT)
    it = iter(source)
    for _ in range(6):  # into the streamed layers: the producer is at its bound
        idxs, segs = next(it)
    _wait_for(lambda: source._q.full())
    if how == "abort_then_close":
        source.abort()
        with pytest.raises(executor_mod.SourceClosed):
            while True:  # what was queued before the abort may still arrive
                idxs, segs = next(it)
    source.close()
    assert source._thread is None
    del it, segs
    import gc

    gc.collect()
    assert _Buffer.alive == 0


def test_cycling_source_keeps_its_bound_over_sweeps(monkeypatch, deep_dir):
    source = _tracked_source(monkeypatch, deep_dir, 2, _RESIDENT, cycle=True)
    try:
        it = iter(source)
        for n in range(3 * len(source.shards)):
            idxs, segs = next(it)
            assert idxs == source.shards[n % len(source.shards)]
            assert _Buffer.alive <= 4 and source._q.qsize() <= 2
        del segs
    finally:
        source.close()
    assert _Buffer.alive == 0


# ---------------------------------------------------------------------------
# The order of enqueue: an upload's slot comes back at "dispatched" (PR 36)
# ---------------------------------------------------------------------------

def _log_puts(monkeypatch, fail=None):
    """Log every ``_assemble_parts`` call (a build's device_put) as ("put", n),
    n the shard's position (calls come in shard order, a retried one again
    with the same n). ``fail(n, attempt)`` True makes that attempt raise."""
    events, lock = [], threading.Lock()
    orig = executor_mod._assemble_parts
    state = {"n": -1, "attempt": 0, "parts": None}

    def logged(parts, *a, **k):
        with lock:
            if parts is not state["parts"]:  # a retry passes the same list
                state.update(n=state["n"] + 1, attempt=0, parts=parts)
            state["attempt"] += 1
            n, attempt = state["n"], state["attempt"]
            events.append(("put", n))
        if fail is not None and fail(n, attempt):
            raise OSError(f"planted: shard {n} attempt {attempt}")
        return orig(parts, *a, **k)

    monkeypatch.setattr(executor_mod, "_assemble_parts", logged)

    def say(*event):
        with lock:
            events.append(event)

    def puts():
        with lock:
            return len({e[1] for e in events if e[0] == "put"})

    return events, say, puts


def _most_built_not_dispatched(events):
    most = held = 0
    seen = set()
    for kind, n in events:
        if kind == "put" and n not in seen:
            seen.add(n)
            held += 1
        elif kind == "dispatched":
            held -= 1
        most = max(most, held)
    return most


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_an_upload_goes_out_behind_the_dispatch_it_was_waiting_for(
    monkeypatch, deep_dir, depth
):
    """For every shard k the device_put of shard k + depth + 1 begins after
    k's last block is dispatched (never before: the consumer dawdles first)
    and before k's shard-end wait returns (the consumer does not take the
    next shard until it has seen that put begin); shards built and not
    dispatched never exceed depth + 1."""
    events, say, puts = _log_puts(monkeypatch)
    source = _tracked_source(monkeypatch, deep_dir, depth, ())
    n = len(source.shards)
    try:
        for k, (idxs, segs) in enumerate(source):
            time.sleep(0.02)  # the blocks' dispatch: the producer has its time
            assert puts() == min(k + depth + 1, n)  # nothing past its slots
            say("dispatched", k)
            source.dispatched()
            source.dispatched()  # a second call returns nothing
            _wait_for(lambda: puts() == min(k + depth + 2, n), timeout_s=5.0)
        del segs
    finally:
        source.close()
    first_put = {}
    for i, (kind, j) in enumerate(events):
        if kind == "put":
            first_put.setdefault(j, i)
    for k in range(n - depth - 1):
        assert events.index(("dispatched", k)) < first_put[k + depth + 1]
    assert _most_built_not_dispatched(events) == depth + 1
    acct = source.account(0.0, time.perf_counter())
    assert acct["producer_blocked_s"] > 0  # the waits for a slot are counted


@pytest.mark.parametrize("how", ["close", "abort_then_close"])
def test_stopping_a_source_whose_producer_waits_for_a_slot(monkeypatch, deep_dir, how):
    built = []
    orig = executor_mod._split_parts

    def logged(loader, layer_idxs, *a, **k):
        out = orig(loader, layer_idxs, *a, **k)
        built.append(layer_idxs)
        return out

    monkeypatch.setattr(executor_mod, "_split_parts", logged)
    events, say, puts = _log_puts(monkeypatch)
    source = _tracked_source(monkeypatch, deep_dir, 2, ())
    it = iter(source)
    idxs, segs = next(it)  # held and never dispatched: no slot comes back
    # The host side of the fourth build is done and its device_put waits.
    _wait_for(lambda: len(built) == 4)
    time.sleep(0.05)
    assert puts() == 3 and source._thread.is_alive()
    t0 = time.monotonic()
    if how == "abort_then_close":
        source.abort()
    source.close()
    assert time.monotonic() - t0 < 1.0
    assert source._thread is None and puts() == 3
    del it, segs
    import gc

    gc.collect()
    assert _Buffer.alive == 0


@pytest.mark.parametrize("cycle", [False, True], ids=["one_sweep", "cycling_two"])
def test_a_consumer_that_never_says_dispatched_is_served_all_the_same(
    monkeypatch, deep_dir, cycle
):
    """The slot of a shard comes back when the consumer asks for the next:
    no deadlock, the same bound, no upload counted as ordered."""
    source = _tracked_source(monkeypatch, deep_dir, 2, (), cycle=cycle)
    want = source.shards * (2 if cycle else 1)
    got = []
    try:
        it = iter(source)
        for _ in want:
            idxs, segs = next(it)
            got.append(idxs)
            assert _Buffer.alive <= 4
        if not cycle:
            with pytest.raises(StopIteration):
                next(it)
        del segs
    finally:
        source.close()
    assert got == want
    assert source.uploads_ordered == 0


def test_a_retried_device_put_takes_one_slot(monkeypatch, deep_dir):
    from flexible_llm_sharding_tpu.faults.retry import RetryPolicy

    events, say, puts = _log_puts(
        monkeypatch, fail=lambda n, attempt: n == 4 and attempt <= 3
    )
    source = _tracked_source(
        monkeypatch, deep_dir, 2, (),
        retry_policy=RetryPolicy(max_attempts=5, base_delay_s=0.001),
    )
    try:
        for idxs, segs in source:
            source.dispatched()
        del segs
    finally:
        source.close()
    assert [e for e in events if e == ("put", 4)] == [("put", 4)] * 4
    # Every build took one slot and every shard returned one.
    assert len(source._slot_ordered) == 3
    assert source._slots.acquire(blocking=False)
    assert source._slots.acquire(blocking=False)
    assert source._slots.acquire(blocking=False)
    assert not source._slots.acquire(blocking=False)


def test_a_device_put_that_fails_for_good_gives_its_slot_back(monkeypatch, deep_dir):
    """The consumer takes a fault in that shard's place, which holds no
    slot: the producer still gets depth + 1 builds past it."""
    from flexible_llm_sharding_tpu.faults.retry import RetryPolicy

    events, say, puts = _log_puts(monkeypatch, fail=lambda n, attempt: n == 4)
    source = _tracked_source(
        monkeypatch, deep_dir, 2, (),
        retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.001),
    )
    try:
        it = iter(source)
        for _ in range(4):
            idxs, segs = next(it)
            source.dispatched()
        with pytest.raises(executor_mod.ShardLoadError):
            next(it)
        # Shards 0-3 came and went, 4 failed: 5, 6 and 7 are built.
        _wait_for(lambda: _Buffer.made == 7, timeout_s=5.0)
        time.sleep(0.05)
        assert _Buffer.made == 7
        del segs
    finally:
        source.close()


def test_a_streamed_tails_uploads_are_all_ordered(monkeypatch, deep_dir):
    """Embedding, norm, head and the first two layers seated; the six layers
    behind them streamed. The seating sweep uploads all eleven files, the
    first depth + 1 on the slots the source starts with; the next sweep's
    six uploads all go out on a slot that "dispatched" returned, each after
    the last block of the shard three before it was dispatched."""
    from flexible_llm_sharding_tpu.runtime import hostcache, residency

    path, names = deep_dir
    size = residency.layer_stream_bytes(path, names, False)
    budget = size[0] + size[1] + size[2] + size[9] + size[10] + 16
    residency.reset_process_tier()
    hostcache.reset_process_cache()
    events, lock = [], threading.Lock()
    orig_put, orig_block = executor_mod._assemble_parts, executor_mod.process_block

    def put(parts, *a, **k):
        with lock:
            events.append(("put", sum(e[0] == "put" for e in events)))
        return orig_put(parts, *a, **k)

    def block(cfg, dtype, segments, visit, store, b, *a, clock=None, **k):
        out = orig_block(cfg, dtype, segments, visit, store, b, *a, clock=clock, **k)
        with lock:
            events.append(("block", clock.shard_idx, b))
        return out

    try:
        ex = StreamingExecutor(
            _account_cfg(path, hbm_pin_gb=budget / 1e9), tokenizer=FakeTokenizer()
        )
        ex(list(PROMPTS))  # seats the pins
        seating = executor_mod.process_sweep_log()[-1]
        with monkeypatch.context() as m:
            m.setattr(executor_mod, "_assemble_parts", put)
            m.setattr(executor_mod, "process_block", block)
            ex(list(PROMPTS))
        rec = executor_mod.process_sweep_log()[-1]
    finally:
        residency.reset_process_tier()
        hostcache.reset_process_cache()
    assert (seating["uploads"], seating["uploads_ordered"]) == (11, 8)
    assert rec["pin_hits"] == 5
    assert (rec["uploads"], rec["uploads_ordered"]) == (6, 6)
    last_block = {}
    for i, e in enumerate(events):
        if e[0] == "block":
            last_block[e[1]] = i
    for k in range(11 - 3):
        assert last_block[k] < events.index(("put", k + 3)), k


# ---------------------------------------------------------------------------
# A seated shard's end waited for one shard later (wait_may_lag)
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_tier():
    """The residency tier and the host cache are the process's: this test's
    seats must not serve the next test."""
    from flexible_llm_sharding_tpu.runtime import hostcache, residency

    residency.reset_process_tier()
    hostcache.reset_process_cache()
    yield residency
    residency.reset_process_tier()
    hostcache.reset_process_cache()


def _keep_every_wait(monkeypatch):
    monkeypatch.setattr(
        executor_mod.ShardWeightSource, "wait_may_lag", lambda self: False
    )


def test_a_seated_pass_waits_for_each_shard_one_shard_later(
    monkeypatch, deep_dir, fresh_tier
):
    """Every layer seated (the second pass; the first seats them, each of
    its shards uploading, and keeps every wait): each shard's end is waited
    for inside the next shard's dispatch, after its first launch, so no
    boundary drains; the span and the stamps of that wait name the shard
    waited on; the scores are bit for bit those of the same pass with every
    wait kept."""
    from flexible_llm_sharding_tpu.obs import trace as obs_trace

    path, _ = deep_dir
    ex = StreamingExecutor(
        _account_cfg(path, hbm_pin_gb=1.0, storage_location="tpu"),
        tokenizer=FakeTokenizer(),
    )
    n = len(ex.plan.shards)
    assert len(make_blocks(ex._tokenize(list(PROMPTS)), 2)) > 1
    ex(list(PROMPTS))
    seating = executor_mod.process_sweep_log()[-1]
    tracer = obs_trace.TRACER
    tracer.clear()
    tracer.enable()
    try:
        lagged = ex(list(PROMPTS))
        spans = tracer.snapshot()
    finally:
        tracer.disable()
        tracer.clear()
    rec = executor_mod.process_sweep_log()[-1]
    _keep_every_wait(monkeypatch)
    kept = ex(list(PROMPTS))
    rec_kept = executor_mod.process_sweep_log()[-1]

    assert (seating["uploads"], seating["waits_deferred"]) == (n, 0)
    # the head stores nothing and has no wait; every other shard's lags
    assert (rec["uploads"], rec["waits_deferred"]) == (0, n - 1)
    assert rec_kept["waits_deferred"] == 0 and rec_kept["drained_s"] > 0.0
    assert rec["drained_shards"] == n - 1 and rec["drained_s"] == 0.0
    _assert_phases_partition(rec)
    for a, b in zip(lagged, kept):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    compute = {s["shard_idx"]: s for s in spans if s["name"] == "compute"}
    ends = [
        s for s in spans if s["name"] == "device_wait" and s["at"] == "shard_end"
    ]
    assert sorted(s["shard_idx"] for s in ends) == list(range(n - 1))
    for w in ends:
        nxt = compute[w["shard_idx"] + 1]
        assert nxt["ts_s"] + nxt["launch_s"] <= w["ts_s"]
        assert w["ts_s"] + w["dur_s"] <= nxt["ts_s"] + nxt["dur_s"] + 1e-6
    assert sum(s["dur_s"] for s in ends) == pytest.approx(
        rec["device_wait_s"], abs=1e-4
    )


@pytest.mark.parametrize(
    "seated_layers, lagging",
    # the third: two streamed layers, so shard 6 follows the last shard
    # that lets an upload out (5, whose "dispatched" lets out shard 8's)
    [(2, {9}), (5, {0, 1, 9}), (6, {0, 1, 2, 6, 9})],
    ids=["head_of_three", "head_of_six", "two_streamed"],
)
def test_a_wait_lags_only_where_no_upload_can_go_out_behind_it(
    monkeypatch, deep_dir, fresh_tier, seated_layers, lagging
):
    """The embedding, the first ``seated_layers`` decoder layers, the norm
    and the head seated, the layers between them streamed, prefetch depth
    2 (shard k's "dispatched" lets out the build of shard k + 3). In the
    second pass a shard's end is waited for one shard later only where it
    uploaded nothing and neither its own "dispatched" nor the next shard's
    lets an upload out: every shard that uploaded, every shard that lets an
    upload out and the shard before it keep the wait at their own end; no
    block of a shard that lets an upload out is dispatched while an earlier
    shard's wait is pending; every upload is still ordered, each put of
    shard k + 3 after the last block of shard k."""
    residency = fresh_tier
    path, names = deep_dir
    size = residency.layer_stream_bytes(path, names, False)
    budget = sum(size[i] for i in (*range(seated_layers + 1), 9, 10)) + 16
    streamed = set(range(seated_layers + 1, 9))
    releasing = {s - 3 for s in streamed}
    events, lock = [], threading.Lock()
    orig_put, orig_block = executor_mod._assemble_parts, executor_mod.process_block
    orig_wait = executor_mod.SweepClock.wait_for_shard

    def put(parts, *a, **k):
        with lock:
            events.append(("put", sum(e[0] == "put" for e in events)))
        return orig_put(parts, *a, **k)

    def block(cfg, dtype, segments, visit, store, b, *a, clock=None, **k):
        out = orig_block(cfg, dtype, segments, visit, store, b, *a, clock=clock, **k)
        with lock:
            events.append(("block", clock.shard_idx, b))
        return out

    def wait(clock, result, stamps):
        orig_wait(clock, result, stamps)
        with lock:  # the shard waited on, and the shard the consumer is on
            events.append(("wait", stamps.shard_idx, clock.shard_idx))

    ex = StreamingExecutor(
        _account_cfg(path, hbm_pin_gb=budget / 1e9, storage_location="tpu"),
        tokenizer=FakeTokenizer(),
    )
    ex(list(PROMPTS))  # seats the pins
    with monkeypatch.context() as m:
        m.setattr(executor_mod, "_assemble_parts", put)
        m.setattr(executor_mod, "process_block", block)
        m.setattr(executor_mod.SweepClock, "wait_for_shard", wait)
        ex(list(PROMPTS))
    rec = executor_mod.process_sweep_log()[-1]
    assert rec["pin_hits"] == 11 - len(streamed)
    assert rec["uploads"] == rec["uploads_ordered"] == len(streamed)
    assert rec["waits_deferred"] == len(lagging)
    waits = [e for e in events if e[0] == "wait"]
    assert sorted(e[1] for e in waits) == list(range(10))  # the head has none
    assert {k for _, k, on in waits if on == k + 1} == lagging
    assert all(on == k for _, k, on in waits if k not in lagging)
    assert not lagging & (streamed | releasing | {r - 1 for r in releasing})
    waited_at = {e[1]: i for i, e in enumerate(events) if e[0] == "wait"}
    for i, e in enumerate(events):
        if e[0] == "block" and e[1] in releasing:
            assert all(waited_at[k] < i for k in range(e[1])), e
    last_block = {}
    for i, e in enumerate(events):
        if e[0] == "block":
            last_block[e[1]] = i
    for k in range(11 - 3):
        assert last_block[k] < events.index(("put", k + 3)), k


@pytest.mark.parametrize("where", ["cpu", "disk", "kept_on_the_chip"])
def test_a_wait_lags_only_where_the_blocks_stay_on_the_chip(
    monkeypatch, deep_dir, fresh_tier, tmp_path, where
):
    """Every layer seated: a ``cpu`` store (no room on the chip: every block
    crosses the link) and a resumable ``disk`` pass keep every wait; with
    nobody saying where and room on the chip for every block (the chip's
    default) every wait but the head's lags."""
    from flexible_llm_sharding_tpu.utils import metrics

    path, _ = deep_dir
    if where == "kept_on_the_chip":
        stats = {"bytes_limit": 1e12, "bytes_in_use": 0.0}
        monkeypatch.setattr(metrics, "device_memory_stats", lambda device=None: stats)
    ex = StreamingExecutor(
        _account_cfg(
            path, hbm_pin_gb=1.0, disk_folder=str(tmp_path),
            storage_location=None if where == "kept_on_the_chip" else where,
        ),
        tokenizer=FakeTokenizer(),
    )
    ex(list(PROMPTS))
    ex(list(PROMPTS))
    rec = executor_mod.process_sweep_log()[-1]
    assert rec["uploads"] == 0
    if where == "kept_on_the_chip":
        assert rec["act_bytes"] == 0
        assert rec["waits_deferred"] == len(ex.plan.shards) - 1
    else:
        assert rec["act_bytes"] > 0 and rec["waits_deferred"] == 0


@pytest.mark.parametrize("where", ["its_own_device", "another_device"])
def test_a_shard_is_handed_over_once_the_newest_upload_to_its_device_arrived(
    monkeypatch, deep_dir, where
):
    """Steps dispatched under an upload in flight would wait for the next
    upload too: next() returns a shard for a device only when the newest
    upload to THAT device has arrived (link_wait_s counts the wait); an
    upload to another chip holds nothing back."""
    arrived = threading.Event()
    waited = []

    def fake_wait(arrays):
        waited.append(arrays)
        assert arrived.wait(10.0)
        return arrays

    monkeypatch.setattr(executor_mod.jax, "block_until_ready", fake_wait)
    d0, d1 = jax.devices()[:2]
    n = len(deep_dir[1])
    source = _tracked_source(
        monkeypatch, deep_dir, 2, (),
        # the first shard alone is for d0; or every shard (device=d0)
        devices=[d0] + [d1] * (n - 1) if where == "another_device" else None,
    )
    got = []
    taker = threading.Thread(target=lambda: got.append(next(iter(source))), daemon=True)
    try:
        _wait_for(lambda: _Buffer.made == 3)  # the three the slots allow are placed
        taker.start()
        taker.join(0.3)
        if where == "its_own_device":
            assert taker.is_alive() and not got and len(waited) == 1
            arrived.set()
            taker.join(5.0)
            assert source.link_wait_s >= 0.25
        else:
            assert not waited and source.link_wait_s == 0.0
        assert not taker.is_alive() and got[0][0] == source.shards[0]
        if where == "its_own_device":
            assert source._in_flight is None  # seen to arrive: held no longer
    finally:
        arrived.set()
        source.close()
    assert source._in_flight is None
    got.clear()
    waited.clear()
    import gc

    gc.collect()
    assert _Buffer.alive == 0


@pytest.fixture(scope="module")
def serial_scores(model_dir):
    """The schedule with no thread and no slots: prefetch_depth 0 builds each
    shard in the consumer's next(), as before this mechanism existed."""
    path, _ = model_dir
    return StreamingExecutor(
        _account_cfg(path, prefetch_depth=0), tokenizer=FakeTokenizer()
    )(list(PROMPTS))


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_scores_are_bit_identical_at_every_prefetch_depth(model_dir, serial_scores, depth):
    path, _ = model_dir
    got = StreamingExecutor(
        _account_cfg(path, prefetch_depth=depth), tokenizer=FakeTokenizer()
    )(list(PROMPTS))
    rec = executor_mod.process_sweep_log()[-1]
    assert rec["uploads"] == 7
    assert rec["uploads_ordered"] == (max(0, 7 - depth - 1) if depth else 0)
    for a, b in zip(got, serial_scores):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_shard_table_dates_an_upload_where_its_call_returned():
    """The watcher's interval opens where the device_put call STARTS (the link
    may carry from there on); for the idle split the upload is enqueued where
    the call returned, 50 ms later here: shard 3's last launch, 20 ms into
    the call, is before it, and nothing waited."""
    from types import SimpleNamespace

    stamps = executor_mod.ShardStamps(3, 0.0, 9.99)
    stamps.t_launch, stamps.t_wait, stamps.t_ready, stamps.t_end = 10.0, 10.03, 10.5, 10.5
    clock = SimpleNamespace(shards=[stamps], block_rows=())
    source = SimpleNamespace(_watcher=None, _produced={6: (0.001, 0.05, 1)})
    intervals = [(8.0, 9.0, 3), (10.01, 10.3, 6)]
    (row,) = executor_mod.ShardWeightSource.shard_table(source, clock, 20.0, intervals)
    assert row["behind_upload_s"] == 0.0 and row["upload_ordered"] == 0
    source._produced[6] = (0.001, 0.001, 1)  # one leaf: enqueued at 10.011
    (row,) = executor_mod.ShardWeightSource.shard_table(source, clock, 20.0, intervals)
    assert row["behind_upload_s"] == pytest.approx(10.3 - 10.011)


def test_shard_table_puts_a_lagged_wait_on_the_shard_it_waited_on():
    """Shard 0's end, waited for from 1.12 to 1.30 inside shard 1's compute
    span (1.06 -> 1.40): the table gives those 0.18 s to shard 0 as its
    device_wait_s, takes them off shard 1's dispatch_s, dates shard 0's
    last launch at its span's end (1.05, before shard 4's upload was
    enqueued at 1.08: nothing queued behind it; dated at the wait's start
    it would read 0.01 s behind that upload) and reads no drain at the
    boundary, shard 1 having launched at 1.10."""
    from types import SimpleNamespace

    s0 = executor_mod.ShardStamps(0, 0.0, 0.99)
    s0.t_launch, s0.t_end, s0.t_wait, s0.t_ready = 1.0, 1.05, 1.12, 1.30
    s1 = executor_mod.ShardStamps(1, 0.01, 1.06)
    s1.t_launch, s1.t_wait, s1.t_ready, s1.t_end = 1.10, 1.32, 1.40, 1.40
    clock = SimpleNamespace(shards=[s0, s1], block_rows=())
    source = SimpleNamespace(_watcher=None, _produced={4: (0.001, 0.001, 1)})
    rows = executor_mod.ShardWeightSource.shard_table(
        source, clock, 2.0, [(1.079, 1.09, 4)]
    )
    got = [
        {k: round(r[k], 9) for k in ("dispatch_s", "device_wait_s", "drained_s",
                                      "behind_upload_s")}
        for r in rows
    ]
    assert got == [
        {"dispatch_s": 0.06, "device_wait_s": 0.18, "drained_s": 0.0,
         "behind_upload_s": 0.0},
        {"dispatch_s": 0.08, "device_wait_s": 0.08, "drained_s": 0.0,
         "behind_upload_s": 0.0},
    ]
    # The two spans' seconds, whole: one wait each, none counted twice.
    assert sum(r["dispatch_s"] + r["device_wait_s"] for r in rows) == pytest.approx(
        (1.05 - 0.99) + (1.40 - 1.06)
    )


def test_runs_are_the_parts_split_parts_builds():
    pinned = frozenset({0, 3, 4, 9})
    runs = lambda idxs, pins: list(executor_mod._runs(idxs, pins))  # noqa: E731
    assert runs((0, 1, 2, 3, 4, 5, 9, 10), pinned) == [
        (True, (0,)), (False, (1, 2)), (True, (3,)), (True, (4,)),
        (False, (5,)), (True, (9,)), (False, (10,)),
    ]
    assert runs((3, 4), pinned) == [(True, (3,)), (True, (4,))]
    assert runs((6, 7), frozenset()) == [(False, (6, 7))]
    built = []

    class _Loader:
        def build_host_shard(self, idxs, streamed=True, upload=True):
            built.append((tuple(idxs), streamed))
            return []

    parts = executor_mod._split_parts(
        _Loader(), (0, 1, 2, 3, 4, 5, 9, 10), pinned, _SeatedTier(pinned), (None,)
    )
    assert [p[:2] for p in parts] == [
        ("pin", 0), ("stream", -1), ("pin", 3), ("pin", 4), ("stream", -1),
        ("pin", 9), ("stream", -1),
    ]
    assert built == [((1, 2), True), ((5,), True), ((10,), True)]
    # No pin set: the whole shard is one build, as before the tier.
    assert [p[:2] for p in executor_mod._split_parts(_Loader(), (6, 7), frozenset())] == [
        ("stream", -1)
    ]


class _SeatingTier(_SeatedTier):
    """A tier whose planned layers are not resident yet: a shard build seats
    each from the sweep's own stream and finds it resident from then on."""

    def __init__(self, pinned):
        super().__init__(pinned)
        self.seats = {}

    def seat_state(self, idx, devices):
        return "seated" if idx in self.seats else "unseated"

    def seated(self, idx, device):
        return self.seats.get(idx)

    def seat(self, idx, device, host, placed):
        return self.seats.setdefault(idx, placed)

    def demote(self, idx):
        raise AssertionError(f"layer {idx} demoted")


def _log_builds(monkeypatch, gate=None, entered=None):
    """Every host build as (layer idxs, streamed, upload), in order; with a
    gate, a build made ahead of its place waits inside for it."""
    builds = []
    real = executor_mod._HostShardLoader._build_host_shard

    def logged(self, layer_idxs, streamed=True, upload=True):
        builds.append((tuple(layer_idxs), streamed, upload))
        if gate is not None and not upload:
            entered.set()
            assert gate.wait(20)
        return real(self, layer_idxs, streamed, upload)

    monkeypatch.setattr(executor_mod._HostShardLoader, "_build_host_shard", logged)
    return builds


_STREAMED = [(i,) for i in range(4, 9)]


def test_seating_sweep_builds_the_streamed_layers_first(monkeypatch, deep_dir):
    """A sweep that will seat layers of the tier: the producer first builds
    the layers that stay streamed, for the cache alone (nothing counted as
    link traffic), so the cache copies them to pinned_host while the sweep
    reads the seats; then the sweep runs in its order, the streamed layers'
    own builds are hits, every file is verified once and the seats are
    cached where there is room. A sweep with everything seated, and one
    whose trees have no pinned_host target, build nothing ahead."""
    from flexible_llm_sharding_tpu.integrity import manifest as iman
    from flexible_llm_sharding_tpu.runtime import hostcache

    builds = _log_builds(monkeypatch)
    iman.reset_verdicts()
    verifies0 = iman.verdict_stats()["full_verifies"]
    cache = hostcache.HostShardCache(budget_bytes=1 << 30)
    tier = _SeatingTier(_RESIDENT)
    before = executor_mod.process_streamed_bytes()
    source = _seating_source(monkeypatch, deep_dir, tier, cache)
    try:
        got = [idxs for idxs, _ in source]
    finally:
        source.close()
    assert got == source.shards
    assert builds[:5] == [(run, True, False) for run in _STREAMED]
    in_order = [(idxs, i not in _RESIDENT, True) for idxs in source.shards for i in idxs]
    assert builds[5:] == in_order
    assert cache.pin_wait()
    s = cache.stats()
    assert (s["misses"], s["hits"], s["entries"]) == (11, 5, 11)
    assert s["pinned_host_copies"] == 5 and s["evictions"] == 0
    assert iman.verdict_stats()["full_verifies"] - verifies0 == 11
    # The seats' bytes and the streamed layers' cross the link once each.
    assert executor_mod.process_streamed_bytes() - before == source.upload_bytes == s["bytes"]
    # Everything seated: no pass ahead, every streamed byte from pinned_host.
    del builds[:]
    again = _seating_source(monkeypatch, deep_dir, tier, cache)
    try:
        assert [idxs for idxs, _ in again] == again.shards
    finally:
        again.close()
    assert builds == [(run, True, True) for run in _STREAMED]
    assert again.upload_pinned_bytes == again.upload_bytes == s["pinned_host_bytes"]
    # No cache, so no pinned_host target: the sweep's own order and nothing else.
    del builds[:]
    plain = _seating_source(monkeypatch, deep_dir, _SeatingTier(_RESIDENT), None)
    try:
        assert [idxs for idxs, _ in plain] == plain.shards
    finally:
        plain.close()
    assert builds == in_order


def _seating_source(monkeypatch, deep_dir, tier, cache):
    return _tracked_source(monkeypatch, deep_dir, 2, _RESIDENT, tier=tier, host_cache=cache)


def test_the_pass_ahead_ends_where_the_cache_starts_to_evict(monkeypatch, deep_dir):
    """A budget of two streamed layers: the third build ahead evicts, the
    pass ends there, and the sweep completes in order."""
    from flexible_llm_sharding_tpu.runtime import hostcache

    path, names = deep_dir
    loader = executor_mod._HostShardLoader(path, names, np.dtype(np.float32))
    one = hostcache._tree_nbytes(loader.build_host_shard((5,)))
    loader.close()
    builds = _log_builds(monkeypatch)
    cache = hostcache.HostShardCache(budget_bytes=int(2.5 * one))
    source = _seating_source(monkeypatch, deep_dir, _SeatingTier(_RESIDENT), cache)
    try:
        got = [idxs for idxs, _ in source]
    finally:
        source.close()
    assert got == source.shards
    assert [b[0] for b in builds if not b[2]] == _STREAMED[:3]
    s = cache.stats()
    # The big seats found no room and pushed nothing out (put(evict=False):
    # tests/test_hostcache.py); two streamed layers fill the budget.
    assert s["bytes"] <= cache.budget_bytes
    with cache._lock:
        assert sum(key[-1] in _STREAMED for key in cache._entries) == 2
        assert not any(key[-1] in [(1,), (2,), (3,)] for key in cache._entries)


def test_stopping_a_source_inside_the_pass_ahead(monkeypatch, deep_dir):
    from flexible_llm_sharding_tpu.runtime import hostcache

    gate, entered = threading.Event(), threading.Event()
    builds = _log_builds(monkeypatch, gate, entered)
    cache = hostcache.HostShardCache(budget_bytes=1 << 30)
    source = _seating_source(monkeypatch, deep_dir, _SeatingTier(_RESIDENT), cache)
    try:
        assert entered.wait(20)
        source.abort()  # the watchdog's, from another thread: no join
        gate.set()
    finally:
        gate.set()
        source.close()
    assert source._thread is None
    assert builds == [(_STREAMED[0], True, False)]  # nothing begun after the stop
    assert _Buffer.alive == 0
