"""Profile the int8 streaming slowdown seen in BENCH r3 (int8_speedup 0.09).

Times, on the live device, each candidate cost in the int8 path
(``runtime/executor.py _place``): host->device transfer by dtype and leaf
granularity, the on-device dequant kernel, and a full int8 shard placement
vs its bf16 twin. Run from the repo root on a machine with the chip:

    python scripts/profile_int8.py
"""
import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp


def timed(fn, iters=5, warm=1):
    for _ in range(warm):
        out = fn()
    jax.device_get(jax.tree.leaves(out)[0].sum())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.device_get(jax.tree.leaves(out)[0].sum())
    return (time.perf_counter() - t0) / iters


def main():
    dev = jax.devices()[0]
    print("device:", dev, file=sys.stderr)
    n = 1024
    bf16 = np.zeros((n, n), np.dtype("bfloat16") if hasattr(np, "bfloat16") else np.float16)
    try:
        import ml_dtypes

        bf16 = np.zeros((n, n), ml_dtypes.bfloat16)
    except ImportError:
        pass
    i8 = np.zeros((n, n), np.int8)
    u32 = i8.view(np.uint32).reshape(n, n // 4)
    sc = np.zeros((n,), np.float32)

    r = {}
    r["put_bf16_2MB"] = timed(lambda: jax.device_put(bf16, dev))
    r["put_int8_1MB"] = timed(lambda: jax.device_put(i8, dev))
    r["put_u32view_1MB"] = timed(lambda: jax.device_put(u32, dev))
    r["put_scale_4KB"] = timed(lambda: jax.device_put(sc, dev))

    # A 7-tensor "layer" as one device_put tree, int8 vs bf16 granularity.
    bf_tree = {f"w{k}": bf16 for k in range(7)}
    q_tree = {f"w{k}": {"q8": i8, "s": sc} for k in range(7)}
    r["put_tree_bf16_x7"] = timed(lambda: jax.device_put(bf_tree, dev))
    r["put_tree_int8_x7"] = timed(lambda: jax.device_put(q_tree, dev))

    # On-device dequant of the placed int8 tree (the _dequant_tree shape).
    from flexible_llm_sharding_tpu.runtime.executor import _dequant_tree

    placed = jax.device_put(q_tree, dev)
    r["dequant_x7"] = timed(lambda: _dequant_tree(placed, "bfloat16"))

    # Full _place of both trees (transfer + dequant dispatch).
    from flexible_llm_sharding_tpu.runtime.executor import _place

    r["place_bf16_seg"] = timed(lambda: _place([("embed", bf_tree)], dev))
    r["place_int8_seg"] = timed(lambda: _place([("embed", q_tree)], dev))

    for k, v in r.items():
        print(f"{k:22s} {v * 1e3:9.2f} ms")


if __name__ == "__main__":
    main()
