"""Test harness: force JAX onto CPU with 8 virtual devices so DP/MP mesh
sharding and pipeline handoff are testable without a TPU slice (SURVEY.md §4)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force even if the env preset a TPU platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# jax may already be imported (a pytest plugin) with another platform preset
# in its config, which the env var above can no longer change; re-pin to CPU
# before any backend is initialised.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Tests neither read nor write the persistent compile cache: cli.main places
# it at <checkout>/.jax_cache (utils/compile_cache.py), and the AOT compiles
# for a described TPU in tests/test_tpu_compile.py cannot be read back
# without a chip.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

from flexible_llm_sharding_tpu.config import LlamaConfig


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: spawns subprocesses / long-running integration tests"
    )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables at module boundaries. The full suite
    accumulates 300+ XLA:CPU compilations in one process and segfaults
    inside backend_compile_and_load near the end (reproducible at ~94%;
    any individual module or the last-8-files tail passes cleanly).
    Diagnosis (scripts/repro_xla_compile_segfault.py): NOT a countable
    executable limit — 800 tiny distinct compiles and 400 suite-shaped
    scan/vmap/donated compiles against the 8-device backend both survive
    with every executable live — but a cumulative compile-path resource
    only the full suite's program mix exhausts (crash site + this host's
    cpu_aot_loader feature-mismatch warnings implicate XLA:CPU's
    compile/load path). Bounding cache growth per module avoids it;
    cross-module cache reuse is negligible (distinct shapes/configs).

    ``FLS_NO_CLEAR_CACHES=1 python -m pytest tests/ -q`` disables the
    mitigation — the full-suite segfault repro as a one-liner (expect
    SIGSEGV near the end of the run).

    Upstream filing: the complete ready-to-file jax-ml/jax issue (title,
    body, environment, isolation results) is
    ``scripts/xla_cpu_segfault_issue.md`` — this rig has no network
    egress, so that file IS the tracking record until an egress-capable
    environment files it and replaces this citation with the issue URL."""
    yield
    # Value-checked ("1"/"true"), not presence-checked: =0 must keep the
    # mitigation ON (skipping it segfaults the suite with no hint why).
    if os.environ.get("FLS_NO_CLEAR_CACHES", "").lower() not in ("1", "true"):
        jax.clear_caches()


@pytest.fixture(scope="session")
def tiny_cfg() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        max_position_embeddings=512,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
