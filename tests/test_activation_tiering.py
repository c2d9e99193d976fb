"""Where nobody set ``storage_location``: the scoring pass keeps on the chip
the blocks that fit a budget derived from the chip's memory account and the
residency tier's plan, and sends the rest the ``cpu`` way; an explicit value
means what it always meant, and every other path reads unset as ``cpu``."""

import dataclasses
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexible_llm_sharding_tpu.config import FrameworkConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime import executor as executor_mod
from flexible_llm_sharding_tpu.runtime import residency
from flexible_llm_sharding_tpu.runtime.activations import ActivationStore
from flexible_llm_sharding_tpu.runtime.executor import StreamingExecutor
from flexible_llm_sharding_tpu.utils import metrics
from flexible_llm_sharding_tpu.utils.checkpoint import layer_names_for, save_params

from tests.fake_tokenizer import FakeTokenizer

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome", " might be Lyon")),
    ("Water boils", (" at 100C", " when heated to its boiling point")),
    ("Two plus two equals", (" four", " five", " twenty-two", " fish")),
]


# -- the store ---------------------------------------------------------------


def _block(seed, b=2, lp=4, s=3, ls=2, d=8, on_device=True):
    rng = np.random.default_rng(seed)
    p = rng.standard_normal((b, lp, d)).astype(np.float32)
    s_ = rng.standard_normal((b, s, ls, d)).astype(np.float32)
    return (jnp.asarray(p), jnp.asarray(s_)) if on_device else (p, s_)


BLOCK_BYTES = sum(a.nbytes for a in _block(0))


@pytest.mark.parametrize("fit", [0, 1, 2, 3])
def test_a_cpu_store_keeps_the_blocks_that_fit_its_budget(tmp_path, fit):
    """Three equal blocks under a budget for ``fit`` of them: those stay
    device arrays and come back as they went in, the others take the host's
    way, and the store's two counters say how the bytes split."""
    st = ActivationStore(
        "cpu", str(tmp_path), np_dtype=np.float32,
        device_budget=fit * BLOCK_BYTES + BLOCK_BYTES // 2,
    )
    blocks = [_block(i) for i in range(3)]
    for b, (p, s) in enumerate(blocks):
        st.store(b, [2 * b, 2 * b + 1], p, s)
    assert st.device_bytes == fit * BLOCK_BYTES
    assert st.link_bytes == (3 - fit) * BLOCK_BYTES
    for b, (p, s) in enumerate(blocks):
        gp, gs = st.fetch(b, [2 * b, 2 * b + 1])
        kept = b < fit
        assert isinstance(gs, jax.Array) is kept and isinstance(gp, jax.Array) is kept
        assert (gp is p and gs is s) if kept else isinstance(gs, np.ndarray)
        np.testing.assert_array_equal(np.asarray(gp), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(s))
    # both ways for what went by the host, nothing for what stayed
    assert st.link_bytes == 2 * (3 - fit) * BLOCK_BYTES
    assert st.device_bytes == fit * BLOCK_BYTES
    st.clear()


def test_a_fetch_gives_its_room_back(tmp_path):
    """The budget bounds what is held AT ONCE: a fetched block's bytes are
    free for the next store, which is how one generation follows another."""
    st = ActivationStore("cpu", str(tmp_path), device_budget=BLOCK_BYTES)
    st.store(0, [0, 1], *_block(0))
    st.store(1, [2, 3], *_block(1))  # no room: the host's
    assert isinstance(st.fetch(0, [0, 1])[1], jax.Array)
    st.store(0, [0, 1], *_block(2))  # the room came back
    assert st.device_bytes == 2 * BLOCK_BYTES and st.link_bytes == BLOCK_BYTES
    assert isinstance(st.fetch(1, [2, 3])[1], np.ndarray)
    assert isinstance(st.fetch(0, [0, 1], with_prefix=False)[1], jax.Array)
    st.clear()


@pytest.mark.parametrize(
    "location,budget,on_device,kept",
    [
        ("tpu", 0, True, True),  # no bound, whatever budget is passed
        ("tpu", 0, False, True),  # and whatever it is handed, as ever
        ("cpu", 0, True, False),  # the plain cpu store
        ("cpu", 10**9, False, False),  # host arrays are not moved up
        ("cpu", 10**9, True, True),
        ("disk", 10**9, True, False),  # the file contract is untouched
    ],
)
def test_only_a_budget_or_tpu_keeps_a_block_on_the_chip(
    tmp_path, location, budget, on_device, kept
):
    st = ActivationStore(
        location, str(tmp_path), np_dtype=np.float32, device_budget=budget
    )
    p, s = _block(0, on_device=on_device)
    st.store(0, [0, 1], p, s)
    assert (st.device_bytes, st.link_bytes) == (
        (BLOCK_BYTES, 0) if kept else (0, BLOCK_BYTES)
    )
    assert (0 in st._on_device) is kept
    gp, gs = st.fetch(0, [0, 1])
    assert gs is s or not kept
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(p))
    st.clear()


def test_a_mixed_store_still_spills_past_max_in_cpu(tmp_path):
    """The three tiers in one pass: the chip while the budget lasts, host
    RAM up to ``max_in_cpu`` prompts, disk past that."""
    st = ActivationStore(
        "cpu", str(tmp_path), np_dtype=np.float32, max_in_cpu=2,
        device_budget=BLOCK_BYTES,
    )
    blocks = [_block(i) for i in range(3)]
    for b, (p, s) in enumerate(blocks):
        st.store(b, [2 * b, 2 * b + 1], p, s)
    assert set(st._on_device) == {0} and st._spilled == {2}
    for b, (p, s) in enumerate(blocks):
        gp, gs = st.fetch(b, [2 * b, 2 * b + 1])
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(s))
        np.testing.assert_array_equal(np.asarray(gp), np.asarray(p))
    st.clear()


@pytest.mark.parametrize("location", ["tpu", "cpu"])
def test_clear_drops_the_device_arrays(tmp_path, location):
    st = ActivationStore(location, str(tmp_path), device_budget=10**9)
    st.store(0, [0, 1], *_block(0))
    st.store(1, [2, 3], *_block(1))
    assert st._device_held == 2 * BLOCK_BYTES
    st.clear()
    assert not st._mem and not st._on_device and st._device_held == 0
    # and the store is as new: the budget is whole again
    st.store(0, [0, 1], *_block(0))
    assert st._device_held == BLOCK_BYTES
    st.clear()


# -- the budget --------------------------------------------------------------


class _Chip:
    platform, device_kind, id = "tpu", "TPU v5 lite", 0

    def __init__(self, limit=16_000, in_use=0):
        self.limit, self.in_use = limit, in_use

    def memory_stats(self):
        return {"bytes_limit": self.limit, "bytes_in_use": self.in_use}


class _Tier:
    """As much of a DeviceResidencyTier as the budget reads."""

    def __init__(self, planned, seated):
        self.planned, self.seated = planned, seated

    def committed_device_bytes(self, device=None):
        return max(self.planned, self.seated)

    def pinned_device_bytes(self, device=None):
        return self.seated

    def max_pinned_device_bytes(self):
        return self.seated


@pytest.mark.parametrize(
    "in_use,planned,seated,in_flight,want",
    [
        (0, 0, 0, 0, 8_000),  # no tier, nothing streaming: half the chip
        (1_000, 0, 0, 4_000, 5_500),  # what others hold comes off first
        (0, 10_000, 0, 4_000, 1_000),  # the seating sweep: by the plan
        (10_000, 10_000, 10_000, 4_000, 1_000),  # seated: the same answer
        (11_000, 10_000, 10_000, 4_000, 500),  # beside someone else's 1,000
        (12_000, 10_000, 12_000, 2_000, 1_000),  # more seated than planned
        (0, 10_000, 0, 6_000, 0),  # nothing left: never negative
        (0, 14_000, 0, 4_000, 0),
    ],
)
def test_budget_is_half_of_what_the_plan_leaves_free(
    monkeypatch, in_use, planned, seated, in_flight, want
):
    """The limit less what others hold, less the pins planned (or seated,
    where that is more), less the shards in flight; the store takes half."""
    tier = _Tier(planned, seated) if planned or seated else None
    monkeypatch.setattr(residency, "process_tier", lambda: tier)
    chip = _Chip(16_000, in_use)
    assert residency.activation_budget_bytes(chip, tier, in_flight) == want
    # a placement resolves to its chip, as for the pin budget
    mesh = type("M", (), {"devices": np.array([chip], dtype=object)})()
    assert residency.activation_budget_bytes(mesh, tier, in_flight) == want


def test_no_memory_account_means_no_budget():
    """The CPU backend reports no stats and has no HBM table entry: 0, so
    tier 1's passes go the ``cpu`` way as they always did."""
    assert residency.activation_budget_bytes(jax.devices()[0], None, 0) == 0


def test_the_tier_commits_its_plan_or_its_seats(tmp_path, tiny_cfg):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    save_params(jax.tree.map(np.asarray, params), str(tmp_path), tiny_cfg)
    names = layer_names_for(tiny_cfg.num_hidden_layers)
    plan = residency.plan_residency(str(tmp_path), names, 10**9)
    tier = residency.DeviceResidencyTier(str(tmp_path), names, plan)
    assert tier.committed_device_bytes(None) == plan.pinned_bytes_est > 0
    tier.pressure_unpin()  # an empty plan, nothing seated
    assert tier.committed_device_bytes(None) == 0


# -- the executor ------------------------------------------------------------


@pytest.fixture(scope="module")
def model_dir(tiny_cfg, tmp_path_factory):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    d = tmp_path_factory.mktemp("tiny_model_tiering")
    save_params(jax.tree.map(np.asarray, params), str(d), tiny_cfg)
    return str(d)


def _fw(model_dir, tmp_path, **kw):
    base = dict(
        model_path=model_dir,
        layer_num_per_shard=1,
        disk_folder=str(tmp_path / "acts"),
        dtype="bfloat16",
        bucket_multiple=8,
        block_size=2,
        prefetch_depth=0,
        hbm_pin_gb=0,
        host_cache_gb=0,
    )
    base.update(kw)
    return FrameworkConfig(**base)


@pytest.fixture
def stores(monkeypatch):
    """Every ActivationStore the executor makes, with each store() call's
    (block, bytes)."""
    made = []

    class Recording(ActivationStore):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.sizes = []
            made.append(self)

        def store(self, block_id, idxs, p, s):
            self.sizes.append(
                (block_id, sum(a.nbytes for a in (p, s) if a is not None))
            )
            return super().store(block_id, idxs, p, s)

    monkeypatch.setattr(executor_mod, "ActivationStore", Recording)
    return made


def _give_the_chip(monkeypatch, cfg, budget):
    """Make the allocator's account read so that the derived budget is
    ``budget``: the seam test_residency.py uses for ``bytes_limit``."""
    names = layer_names_for(4)
    in_flight = residency.in_flight_bytes(cfg, names, False)
    stats = {"bytes_limit": float(in_flight + 2 * budget), "bytes_in_use": 0.0}
    monkeypatch.setattr(metrics, "device_memory_stats", lambda device=None: stats)


def _run(cfg):
    got = StreamingExecutor(cfg, tokenizer=FakeTokenizer())(list(PROMPTS))
    return got, executor_mod.process_sweep_log()[-1]


@pytest.mark.parametrize("fits", ["all", "some", "none"])
def test_unset_derives_where_each_block_goes(model_dir, tmp_path, monkeypatch, stores, fits):
    """Unset: with room for every block the pass is a ``tpu`` pass
    (``act_bytes`` 0, ``act_device_bytes`` the held bytes, no wait inside
    the store), with room for none a ``cpu`` pass, with room for some the
    record says how it split; the scores are the same numbers to the bit
    whichever way a block went."""
    via_cpu, rec_cpu = _run(_fw(model_dir, tmp_path, storage_location="cpu"))
    via_tpu, rec_tpu = _run(_fw(model_dir, tmp_path, storage_location="tpu"))
    assert rec_cpu["act_device_bytes"] == 0 and rec_cpu["act_bytes"] > 0
    assert rec_tpu["act_bytes"] == 0 and rec_tpu["act_wait_s"] == 0
    per_block = dict(stores[-1].sizes[:2])  # the embedding shard's two stores
    assert rec_tpu["act_device_bytes"] == sum(n for _, n in stores[-1].sizes)
    # out and back: twice what was stored, less the prefixes that the norm
    # shard does not fetch
    assert rec_tpu["act_device_bytes"] < rec_cpu["act_bytes"] < 2 * rec_tpu["act_device_bytes"]

    cfg = _fw(model_dir, tmp_path)
    assert cfg.storage_location is None
    budget = {"all": 10**9, "some": max(per_block.values()), "none": 0}[fits]
    _give_the_chip(monkeypatch, cfg, budget)
    got, rec = _run(cfg)
    store = stores[-1]
    assert store.location == "cpu" and store.device_budget == budget
    for g, c, t in zip(got, via_cpu, via_tpu):
        assert np.array_equal(g, c) and np.array_equal(g, t)
    if fits == "all":
        assert rec["act_bytes"] == 0 and rec["act_wait_s"] == 0
        assert rec["act_device_bytes"] == rec_tpu["act_device_bytes"]
    elif fits == "none":
        assert rec["act_device_bytes"] == 0
        assert rec["act_bytes"] == rec_cpu["act_bytes"]
    else:
        assert 0 < rec["act_device_bytes"] < rec_tpu["act_device_bytes"]
        assert 0 < rec["act_bytes"] < rec_cpu["act_bytes"]
    # the gauges carry the new key beside the old
    stats = executor_mod.stream_stats()
    assert stats["last_sweep_act_device_bytes"] == rec["act_device_bytes"]
    assert stats["last_sweep_act_bytes"] == rec["act_bytes"]
    assert "act_device_bytes" in executor_mod.SWEEP_RECORD_HELP


@pytest.mark.parametrize(
    "given,location", [("cpu", "cpu"), ("tpu", "tpu"), ("disk", "disk"), ("gpu", "tpu")]
)
def test_an_explicit_location_is_never_overridden(
    model_dir, tmp_path, monkeypatch, stores, given, location
):
    """Room for everything on the chip changes nothing for a user who said
    where the activations go."""
    cfg = _fw(model_dir, tmp_path, storage_location=given)
    _give_the_chip(monkeypatch, cfg, 10**9)
    _, rec = _run(cfg)
    assert stores[-1].location == location
    assert stores[-1].device_budget == (float("inf") if location == "tpu" else 0)
    assert (rec["act_device_bytes"] > 0) is (location == "tpu")
    assert (rec["act_bytes"] > 0) is (location != "tpu")


@pytest.mark.parametrize("ends", ["returns", "raises"])
def test_nothing_of_the_store_outlives_the_pass(
    model_dir, tmp_path, monkeypatch, stores, ends
):
    """``store.clear()`` on both exits: the next thing on the chip (the
    benchmark's float32 reference, the next batch) finds the room."""
    cfg = _fw(model_dir, tmp_path)
    _give_the_chip(monkeypatch, cfg, 10**9)
    ex = StreamingExecutor(cfg, tokenizer=FakeTokenizer())
    if ends == "raises":
        orig = StreamingExecutor._stream_shard
        seen = []

        def bombed(self, store, *a, **kw):
            if len(seen) == 3:  # mid-pass: blocks of shard 2 are held
                assert store._device_held > 0
                raise RuntimeError("boom")
            seen.append(1)
            return orig(self, store, *a, **kw)

        monkeypatch.setattr(StreamingExecutor, "_stream_shard", bombed)
        with pytest.raises(RuntimeError, match="boom"):
            ex(list(PROMPTS))
    else:
        ex(list(PROMPTS))
    store = stores[-1]
    assert store.device_bytes > 0  # it did keep blocks on the chip
    assert not store._mem and not store._on_device and store._device_held == 0


# -- everyone else reads unset as cpu ----------------------------------------


@pytest.mark.parametrize("build", ["dataclass", "batch", "serve"])
def test_the_default_is_unset_everywhere(model_dir, build):
    from flexible_llm_sharding_tpu import cli

    if build == "dataclass":
        got = FrameworkConfig(model_path=model_dir).storage_location
    elif build == "batch":
        args = cli.build_parser().parse_args(
            ["--model_path", model_dir, "--prompt_pickle", "-", "--output_file", "-"]
        )
        assert args.storage_location is None
        got = cli.config_from_args(args).storage_location
    else:
        got = cli.build_serve_parser().parse_args(
            ["--model_path", model_dir]
        ).storage_location
    assert got is None
    with pytest.raises(ValueError, match="storage_location"):
        FrameworkConfig(model_path=model_dir, storage_location="auto")


@pytest.mark.parametrize("path", ["kv_on_device", "decode", "pipeline", "resume"])
def test_other_paths_read_unset_as_cpu(model_dir, tmp_path, monkeypatch, tiny_cfg, path):
    """Room on the chip or not: KV decode, the MP pipeline and ``--resume``
    do under unset what they do under ``cpu`` (``tpu`` stays the user's
    order there: KV in HBM whatever its size, the chip-to-chip hop)."""
    unset = _fw(model_dir, tmp_path, dtype="float32", num_gen_token=2)
    cpu = _fw(
        model_dir, tmp_path, dtype="float32", num_gen_token=2, storage_location="cpu"
    )
    _give_the_chip(monkeypatch, unset, 10**9)
    if path == "kv_on_device":
        from flexible_llm_sharding_tpu.runtime.schedcore import SchedCore
        from flexible_llm_sharding_tpu.runtime.tokenization import (
            PromptTokenizer,
            make_blocks,
        )

        tok = PromptTokenizer(FakeTokenizer(), bucket_multiple=8)
        toks = [tok(p, s) for p, s in PROMPTS]
        blocks = make_blocks(toks, 2)

        def kv(c, resident):
            return SchedCore(c).kv_on_device(
                tiny_cfg, "float32", toks, blocks, 1, resident
            )

        for resident in (False, True):
            assert kv(unset, resident) is kv(cpu, resident)
        assert kv(unset, False) is False
        assert kv(_fw(model_dir, tmp_path, storage_location="tpu"), False) is True
    elif path == "decode":
        from flexible_llm_sharding_tpu.runtime.decode import DecodeGenerator

        outs = []
        for c in (unset, cpu):
            gen = DecodeGenerator(c, tokenizer=FakeTokenizer())
            scores, _ = gen(list(PROMPTS))
            outs.append((scores, gen.stats["decode_kv_on_device"]))
        assert outs[0][1] == outs[1][1]
        for a, b in zip(outs[0][0], outs[1][0]):
            assert np.array_equal(a, b)
    elif path == "pipeline":
        from flexible_llm_sharding_tpu.runtime import pipeline

        made = []

        class Recording(ActivationStore):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                made.append(self)

        monkeypatch.setattr(pipeline, "ActivationStore", Recording)
        outs = [
            pipeline.PipelineRunner(c, jax.devices()[:2], tokenizer=FakeTokenizer())(
                list(PROMPTS)
            )
            for c in (unset, cpu)
        ]
        assert [(s.location, s.device_budget) for s in made] == [("cpu", 0)] * 2
        assert all(s.device_bytes == 0 for s in made)
        for a, b in zip(*outs):
            assert np.array_equal(a, b)
    else:
        for c in (unset, cpu):
            c = dataclasses.replace(c, resume=True, num_gen_token=1)
            ex = StreamingExecutor(c, tokenizer=FakeTokenizer())
            toks = ex._tokenize(list(PROMPTS))
            store = ActivationStore("cpu", c.disk_folder)
            # a derived store is never resumable, exactly as cpu is not:
            # no marker is read or written, the pass starts at shard 0
            assert ex._resume_start(store, ex._resume_signature(toks)) == 0
            ex(list(PROMPTS))
            assert not glob.glob(os.path.join(c.disk_folder, "progress*.json"))
