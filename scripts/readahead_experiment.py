"""Experiment (VERDICT r3 weak #4): can NEXT-shard file readahead beat the
no-readahead cold cast stream anywhere reachable on this host?

Three warming strategies for shard t+1 while shard t is cast:
  none    — baseline (no readahead)
  fadvise — one ``posix_fadvise(WILLNEED)`` call per upcoming file from
            Python: the KERNEL schedules async readahead (DMA), ~zero CPU
            stolen from the cast — viable even on a 1-core host
  pool    — the native C++ pool (native/fileprefetch.cpp) AS CURRENTLY
            BUILT. Historical note: the pool's original warm loop streamed
            the whole file through a userspace pread and measured
            0.66-0.88x on this 1-core host (it stole the cast's CPU; that
            implementation is in git history before the fadvise-only
            rework). The reworked fadvise-only pool measures 1.20x here —
            re-running this script measures whatever fileprefetch.cpp now
            does, not the historical pread numbers.

Measured (2026-07-31, 1-core host, 0.53 GB 16-layer model, 6 rotated reps):
  old pread pool 0.875x | python fadvise 1.05-1.11x | fadvise pool 1.199x

Interleaved reps with ROTATED mode order (the rig's effective disk speed
drifts across passes; a fixed order flatters later slots) and page-cache
eviction (native FADV_DONTNEED) before every pass; eviction failure aborts
(a warm pass labelled cold corrupts the comparison). Usage:
  python scripts/readahead_experiment.py <split_model_dir> [reps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a host-path experiment: CPU backend

from flexible_llm_sharding_tpu.config import LlamaConfig
from flexible_llm_sharding_tpu.runtime.executor import (
    _HostShardLoader,
    np_dtype_for,
)
from flexible_llm_sharding_tpu.utils import checkpoint as ckpt
from flexible_llm_sharding_tpu.utils.native import drop_file_cache


def main() -> None:
    model_path = sys.argv[1]
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    cfg = LlamaConfig.from_pretrained(model_path)
    names = ckpt.layer_names_for(
        cfg.num_hidden_layers, cfg.tie_word_embeddings
    )
    files = [
        os.path.join(model_path, f"{n}{ckpt.LAYER_FILE_SUFFIX}")
        for n in names
    ]
    total_gb = sum(os.path.getsize(f) for f in files) / 1e9
    f32 = np_dtype_for("float32")  # cast path: every byte read + converted

    def one_pass(mode: str) -> float:
        loader = _HostShardLoader(
            model_path, names, f32,
            readahead="on" if mode == "pool" else "off",
        )
        t0 = time.perf_counter()
        for i in range(len(names)):
            if i + 1 < len(names):
                if mode == "pool":
                    loader.warm((i + 1,))
                elif mode == "fadvise":
                    # The production Python fallback itself, so the
                    # measured strategy IS the shipped one.
                    from flexible_llm_sharding_tpu.utils.native import (
                        FilePrefetcher,
                    )

                    FilePrefetcher._py_warm(files[i + 1])
            segs = loader.build_host_shard((i,))
            del segs
        dt = time.perf_counter() - t0
        loader.close()
        return dt

    results: dict[str, list[float]] = {"none": [], "fadvise": [], "pool": []}
    one_pass("none")  # warm imports/allocators once; timing starts cold below
    modes = ("none", "fadvise", "pool")
    for rep in range(reps):
        # Rotate the slot order per rep: the rig's effective disk speed
        # drifts (hypervisor-level caching warms across passes even though
        # the guest page cache is evicted every pass), so a fixed order
        # systematically flatters the later slots.
        order = modes[rep % 3:] + modes[: rep % 3]
        for mode in order:
            assert drop_file_cache(*files), "page-cache eviction failed"
            dt = one_pass(mode)
            results[mode].append(dt)
            print(
                f"rep{rep} {mode:8s}: {dt:6.2f}s  {total_gb / dt:5.2f} GB/s",
                flush=True,
            )
    import numpy as np

    base = float(np.median(results["none"]))
    for mode in ("fadvise", "pool"):
        med = float(np.median(results[mode]))
        print(
            f"{mode}: median {med:.2f}s  speedup vs none "
            f"{base / med:.3f}x (>1 = readahead wins)",
            flush=True,
        )


if __name__ == "__main__":
    main()
