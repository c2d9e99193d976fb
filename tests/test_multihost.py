"""Multi-host (DCN) execution: two REAL processes coordinating through
``jax.distributed`` over localhost, each scoring its own prompt slice on its
local CPU device through the actual CLI — the cluster-free evidence for the
SURVEY §2.3 comm-backend obligation (the reference tops out at one process,
``/root/reference/main.py:59-76``)."""

import os
import pickle
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax

from flexible_llm_sharding_tpu.config import FrameworkConfig
from flexible_llm_sharding_tpu.models import llama
from flexible_llm_sharding_tpu.runtime.orchestration import run_prompts
from flexible_llm_sharding_tpu.utils.checkpoint import save_params

from tests.fake_tokenizer import FakeTokenizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROMPTS = [
    ("The capital of France", (" is Paris", " is Rome")),
    ("Water boils", (" at 100C", " when heated")),
    ("Two plus two equals", (" four", " five")),
]

CHILD = """
import sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {root!r} + "/tests")
import jax
jax.config.update("jax_platforms", "cpu")  # whatever the environment preset
from flexible_llm_sharding_tpu import cli
from fake_tokenizer import FakeTokenizer

cli.main(
    [
        "--model_path", {model!r},
        "--prompt_pickle", {ppkl!r},
        "--output_file", {opkl!r},
        "--dtype", "float32",
        "--num_gen_token", {n_gen!r},
        "--kv_cache", {kv!r},
        "--coordinator_address", {coord!r},
        "--num_processes", "2",
        "--process_id", sys.argv[1],
    ],
    tokenizer=FakeTokenizer(),
)
"""


@pytest.mark.slow
@pytest.mark.parametrize("kv_cache", [False, True])
def test_two_process_cluster_matches_single(tiny_cfg, tmp_path, kv_cache):
    params = llama.init_params(jax.random.PRNGKey(0), tiny_cfg)
    model = tmp_path / "model"
    save_params(jax.tree.map(np.asarray, params), str(model), tiny_cfg)

    ppkl = tmp_path / "p.pkl"
    opkl = tmp_path / "s.pkl"
    with open(ppkl, "wb") as f:
        pickle.dump(PROMPTS, f)

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    child = tmp_path / "child.py"
    child.write_text(
        CHILD.format(
            root=ROOT,
            model=str(model),
            ppkl=str(ppkl),
            opkl=str(opkl),
            coord=f"localhost:{port}",
            n_gen="2" if kv_cache else "1",
            kv="true" if kv_cache else "false",
        )
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="",  # one CPU device per process
    )
    # stderr to FILES, not pipes: two interdependent ranks with undrained
    # PIPEs can deadlock (rank 1 blocks on a full pipe, rank 0 blocks on a
    # collective waiting for rank 1, the test drains rank 0 first).
    err_paths = [tmp_path / f"rank{r}.stderr" for r in range(2)]
    procs = []
    try:
        for rank in range(2):
            with open(err_paths[rank], "wb") as ef:
                procs.append(
                    subprocess.Popen(
                        [sys.executable, str(child), str(rank)],
                        env=env,
                        stderr=ef,
                        cwd=ROOT,
                    )
                )
        for p in procs:
            p.wait(timeout=600)
    finally:
        for p in procs:  # a wedged coordinator must not outlive the test
            if p.poll() is None:
                p.kill()
    for rank, p in enumerate(procs):
        assert p.returncode == 0, err_paths[rank].read_text(errors="replace")[-2000:]

    # Each rank wrote its contiguous slice (array_split: rank0 gets 2 of 3).
    with open(f"{opkl}.rank0", "rb") as f:
        r0 = pickle.load(f)
    with open(f"{opkl}.rank1", "rb") as f:
        r1 = pickle.load(f)
    assert len(r0) == 2 and len(r1) == 1

    cfg = FrameworkConfig(
        model_path=str(model),
        dtype="float32",
        prefetch_depth=0,
        num_gen_token=2 if kv_cache else 1,
    )
    if kv_cache:
        from flexible_llm_sharding_tpu.runtime.orchestration import run_decode

        want, _, _ = run_decode(
            cfg, PROMPTS, tokenizer=FakeTokenizer(), devices=jax.devices()[:1]
        )
    else:
        want = run_prompts(
            cfg, PROMPTS, tokenizer=FakeTokenizer(), devices=jax.devices()[:1]
        )
    for got, exp in zip(r0 + r1, want):
        np.testing.assert_allclose(got[:, 0], np.asarray(exp)[:, 0], rtol=1e-5, atol=1e-6)

    # Rank-suffixed updated-prompt files exist with each slice's prompts.
    for rank, n in ((0, 2), (1, 1)):
        with open(tmp_path / f"p_updated.rank{rank}.pkl", "rb") as f:
            assert len(pickle.load(f)) == n
