"""AOT compile rehearsal (no chip): the score step programs at the
published widths and the cells' buckets, for a described ``v5e:2x2``'s first
chip. Run by hand before a chip call:

    JAX_PLATFORMS=cpu python benchmark/tools/aot_rehearsal.py [config ...]

Prints ``memory_analysis()`` per program. A compile that passes is not a
chip run."""

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
jax.default_backend = lambda: "tpu"  # the kernels pick interpret mode from this

from benchmark import traffic as tr, weights  # noqa: E402
from flexible_llm_sharding_tpu.config import LlamaConfig  # noqa: E402
from flexible_llm_sharding_tpu.runtime import executor  # noqa: E402

BF16 = jnp.bfloat16


def layer_shapes(model, name, sharding, stack=False):
    flat = {}
    for k, shape, _ in weights.tensor_specs(model, name):
        shape = ((1,) + tuple(shape)) if stack else tuple(shape)
        flat[k] = jax.ShapeDtypeStruct(shape, BF16, sharding=sharding)
    return weights.unflatten(flat)


def report(name, compiled, dt):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "program": name, "compile_s": round(dt, 1),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "argument_gb": round(m.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(m.output_size_in_bytes / 1e9, 3),
        "temp_gb": round(m.temp_size_in_bytes / 1e9, 3),
        "alias_gb": round(m.alias_size_in_bytes / 1e9, 3),
    }), flush=True)


def main(names):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
            model = json.load(f)
        model.pop("rehearsal", None)
        cfg = LlamaConfig.from_hf_config(weights.hf_config(model))
        d = cfg.hidden_size
        t = tr.load_traffic("score-b8")
        n, ns = int(t["prompts"]), int(t["suffixes"])
        buckets = {}
        for p in tr.quantile_lengths(t["prefix_tokens"], n):
            b = tr.bucket(p + 1)
            buckets[b] = buckets.get(b, 0) + 1
        ls = tr.bucket(max(tr.quantile_lengths(t["suffix_tokens"], n * ns)))
        print(json.dumps({"config": name, "score_blocks": buckets, "suffix_bucket": ls}))
        lp, b = max(buckets), max(buckets.values())
        for kind, lname in (("dense", "model.layers.0"), ("moe", "model.layers.1")):
            seg = {"layers": layer_shapes(model, lname, one, stack=True),
                   "sliding": None, "rope": None}
            t0 = time.time()
            c = executor._decoder_block.lower(
                cfg, seg, s((b, lp, d), BF16), s((b, ns, ls, d), BF16),
                s((b,), jnp.int32), True, None, None).compile()
            report(f"{name} score _decoder_block {kind} B={b} Lp={lp} S={ns} Ls={ls}",
                   c, time.time() - t0)
        t0 = time.time()
        c = executor._head_block.lower(
            cfg, layer_shapes(model, "lm_head", one), s((b, ns, 1, d), BF16)).compile()
        report(f"{name} score _head_block", c, time.time() - t0)


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    main(args or ["moonlight-16b-a3b", "kanana-2-30b-a3b"])
