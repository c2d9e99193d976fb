"""The bring-up rules of PR 21, on the CPU: no path hides the device.

- the compile cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``) or
  at one fixed in-checkout path, never anywhere else;
- an unknown TPU kind is an error where the CPU keeps ``None``;
- the auto gates propagate a backend error instead of answering "off";
- ``chip_smoke.py`` runs to the end only on a TPU or as an explicit,
  truthfully labelled CPU rehearsal;
- the replica fleet puts one replica on each device.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from flexible_llm_sharding_tpu.config import FrameworkConfig
from flexible_llm_sharding_tpu.utils import compile_cache, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placed_from_outside(
    env_set, monkeypatch, tmp_path, restore_cache_dir
):
    jax.config.update("jax_compilation_cache_dir", "untouched")
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; code sets no directory.
        assert jax.config.jax_compilation_cache_dir == "untouched"
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind

    def memory_stats(self):
        return {"bytes_in_use": 1} if self.platform == "tpu" else None


@pytest.mark.parametrize("fn", [metrics.chip_peak_flops, metrics.chip_hbm_gb])
def test_unknown_tpu_kind_raises_cpu_keeps_none(fn):
    assert fn(_FakeDevice("cpu", "cpu")) is None
    assert fn(_FakeDevice("tpu", "TPU v5 lite")) > 0
    with pytest.raises(ValueError, match="TPU v99"):
        fn(_FakeDevice("tpu", "TPU v99"))


def test_tpu_without_memory_stats_raises():
    dev = _FakeDevice("tpu", "TPU v5 lite")
    dev.memory_stats = lambda: None
    with pytest.raises(RuntimeError, match="memory_stats"):
        metrics.device_memory_stats(dev)
    assert metrics.device_memory_stats(_FakeDevice("cpu", "cpu")) == {}


@pytest.mark.parametrize("gate", ["pallas_enabled", "effective_prefetch_depth"])
def test_auto_gates_propagate_backend_error(gate, monkeypatch):
    def down():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "devices", down)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        getattr(FrameworkConfig(model_path="unused"), gate)()


def _smoke(work, *args):
    # The cache goes where the environment says: nothing lands in the checkout.
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(work / "jax_cache"),
    )
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a machine with no chip
    args = (*args, "--work", str(work / "w"))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, timeout=600,
    )


def test_chip_smoke_refuses_without_a_chip(tmp_path):
    proc = _smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 'tpu'" in proc.stderr


def test_chip_smoke_cpu_rehearsal_runs_to_the_end(tmp_path):
    proc = _smoke(tmp_path, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()]
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }
    phases = [l.get("phase") for l in lines]
    for phase in ("device", "build_hf", "score", "oracle", "decode", "serve"):
        assert phase in phases
    assert sum('"ok"' in l for l in proc.stdout.splitlines()) == 1


def test_fleet_places_one_replica_per_device(tmp_path, tiny_cfg):
    import numpy as np

    from flexible_llm_sharding_tpu.config import ServeConfig
    from flexible_llm_sharding_tpu.models import llama
    from flexible_llm_sharding_tpu.serve import ReplicaFleet
    from flexible_llm_sharding_tpu.utils.checkpoint import save_params
    from tests.fake_tokenizer import FakeTokenizer

    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    save_params(jax.tree.map(np.asarray, params), str(tmp_path), tiny_cfg)
    fleet = ReplicaFleet(
        FrameworkConfig(model_path=str(tmp_path), dtype="float32"),
        ServeConfig(replicas=4), tokenizer=FakeTokenizer(), start=False,
    )
    try:
        devices = [r["device"] for r in fleet.stats()["replicas"].values()]
        assert devices == [str(d) for d in jax.local_devices()[:4]]
    finally:
        fleet.shutdown(drain=False)


@pytest.mark.parametrize("gap, ok", [(0.05, True), (2.0, False)])
def test_generations_may_part_only_at_a_near_tie(gap, ok):
    """chip_smoke's rule for two greedy generations: a pick may differ from
    the reference's only where the reference's own log-prob gap between the
    two is inside LOGP_TOL; what follows the parting is excused."""
    import numpy as np

    sys.path.insert(0, ROOT)
    import chip_smoke

    rng = np.random.default_rng(0)
    logits = rng.standard_normal((1, 3, 500))
    logits[0, :, 7] = 9.0  # a clear winner at every step
    ref = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    got = ref.copy()
    # Step 1: the other side prefers token 8, which the reference puts `gap`
    # below its own pick; step 2 (a different context by then) is garbage.
    ref[0, 1, 8] = ref[0, 1, 7] * np.exp(-gap)
    got[0, 1, 8], got[0, 1, 7] = ref[0, 1, 7], ref[0, 1, 8]
    got[0, 2] = got[0, 2, ::-1]
    if ok:
        out = chip_smoke._compare_generations([got], [ref], "t")
        assert out["generations_identical"] == 0 and out["rows"] == 2
    else:
        with pytest.raises(SystemExit, match="over tolerance"):
            chip_smoke._compare_generations([got], [ref], "t")
