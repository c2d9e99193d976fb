"""Seeded weights for a ``deepseek_v3`` configuration, and the direct writer.

Every tensor is N(0, ``init_std``) in bfloat16 (norm scales are 1), made on
the device from ``--seed`` one tensor at a time; the same call gives the
plain reference its weights, so the reference takes nothing the program made.

The writer puts the tensors straight into the per-layer layout the program's
loader reads (what ``prepare_weights.py`` emits: one safetensors file per
layer name, kernels ``[in, out]``, experts stacked ``[E, in, out]``, an
integrity manifest), with no HF checkpoint and no conversion pass.

Disk: a run may write a few GiB at the most (the driver counts the bytes a
machine has written), and the model is ~18 GB. So expert layers repeat with
period ``distinct_expert_layers`` (the configuration file states it): layer
``first_dense + j`` carries the tensors of slot ``j % period`` and its file
is a hard link to that slot's file. The program still opens, maps, verifies
and streams one file per layer, byte for byte what a checkpoint of distinct
layers costs it; only the bytes written shrink (and the page cache's working
set: PERF.md section 4 has the chip run with every layer distinct beside it).
The period is 3, co-prime with the program's prefetch depth of 2: a shard
that is stale or pinned by one or two layers carries other weights than the
reference's, so the comparison sees it.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SUFFIX = ".safetensors"


def layer_names(model: dict) -> list[str]:
    """Execution order of the per-layer files (the reference's own rule)."""
    n = int(model["num_hidden_layers"])
    return (
        ["model.embed_tokens"]
        + [f"model.layers.{i}" for i in range(n)]
        + ["model.norm", "lm_head"]
    )


def is_moe_layer(model: dict, i: int) -> bool:
    return bool(model.get("n_routed_experts")) and i >= int(
        model.get("first_k_dense_replace", 0)
    )


def slot_of(model: dict, name: str) -> str:
    """The weight slot a layer name draws its tensors from. Expert layers
    cycle with period ``distinct_expert_layers``; every other name is its own
    slot."""
    if name.startswith("model.layers."):
        i = int(name.rsplit(".", 1)[1])
        if is_moe_layer(model, i):
            period = int(model.get("distinct_expert_layers") or 0)
            j = i - int(model.get("first_k_dense_replace", 0))
            return f"moe.{j % period if period else j}"
        return f"dense.{i}"
    return name


def tensor_specs(model: dict, name: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """(native flat key, shape, random?) of one layer file, in a fixed order.
    Shapes follow the HF ``DeepseekV3`` modules, kernels stored [in, out]."""
    d = int(model["hidden_size"])
    v = int(model["vocab_size"])
    if name == "model.embed_tokens":
        return [("embedding", (v, d), True)]
    if name == "model.norm":
        return [("scale", (d,), False)]
    if name == "lm_head":
        return [("kernel", (d, v), True)]
    i = int(name.rsplit(".", 1)[1])
    h = int(model["num_attention_heads"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, kvr = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    if model.get("q_lora_rank"):
        raise NotImplementedError("q_lora_rank: neither configuration uses it")
    out = [
        ("input_layernorm.scale", (d,), False),
        ("post_attention_layernorm.scale", (d,), False),
        ("attn.wq", (d, h * (dn + dr)), True),
        ("attn.kv_a", (d, kvr + dr), True),
        ("attn.kv_a_norm", (kvr,), False),
        ("attn.kv_b", (kvr, h * (dn + dv)), True),
        ("attn.wo", (h * dv, d), True),
    ]
    if is_moe_layer(model, i):
        e, f = int(model["n_routed_experts"]), int(model["moe_intermediate_size"])
        fs = f * int(model.get("n_shared_experts") or 0)
        out += [
            ("mlp.router", (d, e), True),
            ("mlp.correction_bias", (e,), True),
            ("mlp.gate", (e, d, f), True),
            ("mlp.up", (e, d, f), True),
            ("mlp.down", (e, f, d), True),
        ]
        if fs:
            out += [
                ("mlp.shared_gate", (d, fs), True),
                ("mlp.shared_up", (d, fs), True),
                ("mlp.shared_down", (fs, d), True),
            ]
    else:
        f = int(model["intermediate_size"])
        out += [
            ("mlp.gate", (d, f), True),
            ("mlp.up", (d, f), True),
            ("mlp.down", (f, d), True),
        ]
    return out


def _slot_id(slot: str) -> int:
    """A small stable integer per slot, folded into the key."""
    kind, _, idx = slot.partition(".")
    base = {"model": 0, "lm_head": 1, "dense": 1000, "moe": 2000}[kind]
    if kind == "model":
        return {"embed_tokens": 10, "norm": 11}[idx]
    return base + (int(idx) if idx else 0)


@functools.lru_cache(maxsize=None)
def _gen(shape: tuple[int, ...], std: float):
    """One jitted generator per shape: bf16 N(0, std) on the default device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda key: (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)
    )


def layer_tensors(model: dict, seed: int, name: str) -> dict:
    """Device arrays (bf16) of one layer name, from the seed alone."""
    import jax
    import jax.numpy as jnp

    std = float(model.get("init_std", 0.02))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), _slot_id(slot_of(model, name)))
    out = {}
    for t, (k, shape, rand) in enumerate(tensor_specs(model, name)):
        if rand:
            out[k] = _gen(shape, std)(jax.random.fold_in(key, t))
        else:
            out[k] = jnp.ones(shape, jnp.bfloat16)
    return out


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for k, val in flat.items():
        node = tree
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def hf_config(model: dict) -> dict:
    """The ``config.json`` the program parses: the configuration as run,
    minus the benchmark's own keys."""
    own = {"init_std", "distinct_expert_layers", "rehearsal", "assumed", "source",
           "published", "deployment"}
    cfg = {k: v for k, v in model.items() if k not in own}
    cfg.setdefault("architectures", ["DeepseekV3ForCausalLM"])
    cfg.setdefault("torch_dtype", "bfloat16")
    return cfg


def write_model(model: dict, seed: int, out_dir: str) -> dict:
    """Write the per-layer files for ``model`` under ``out_dir`` (emptied
    first). Returns {"bytes_written", "bytes_model", "files"}."""
    from safetensors.numpy import save_file

    # The manifest is part of the layout the loader verifies against; its
    # entry format is the program's own (as a checkpoint tool would use it).
    from flexible_llm_sharding_tpu.integrity import manifest as integrity

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    names = layer_names(model)
    first_of: dict[str, str] = {}
    entries: dict[str, dict] = {}
    written = total = 0

    def store(name: str, flat_np: dict) -> dict:
        fn = f"{name}{SUFFIX}"
        save_file(flat_np, os.path.join(out_dir, fn))
        return integrity.layer_entry(flat_np, fn)

    with ThreadPoolExecutor(max_workers=4) as pool:
        futures = {}
        for name in names:
            slot = slot_of(model, name)
            if slot in first_of:
                continue
            first_of[slot] = name
            dev = layer_tensors(model, seed, name)
            flat_np = {k: np.ascontiguousarray(np.asarray(a)) for k, a in dev.items()}
            del dev
            futures[name] = (pool.submit(store, name, flat_np),
                             sum(a.nbytes for a in flat_np.values()))
            del flat_np
        for name, (fut, nbytes) in futures.items():
            entries[name] = fut.result()
            written += nbytes
    for name in names:
        src = first_of[slot_of(model, name)]
        size = futures[src][1]
        total += size
        if name != src:
            fn = f"{name}{SUFFIX}"
            os.link(os.path.join(out_dir, f"{src}{SUFFIX}"), os.path.join(out_dir, fn))
            entries[name] = {**entries[src], "file": fn}
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config(model), f)
    with open(os.path.join(out_dir, "fls_tpu_layout.json"), "w") as f:
        json.dump({"layout": "native", "dtype": "bfloat16", "layers": names}, f)
    integrity.write_manifest(out_dir, {n: entries[n] for n in names})
    return {"bytes_written": written, "bytes_model": total, "files": len(names)}
