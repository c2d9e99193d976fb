"""Seeded weights for a ``minicpm_sala`` configuration, and the direct writer
(the ``deepseek_v3`` module ``benchmark/weights.py`` with this family's
tensors; the generic pieces are imported from it).

Two layer kinds in the order the configuration's ``mixer_types`` lists:
``lightning-attn`` (q, k, v, o and an output gate of ``lightning_nh`` heads of
``lightning_head_dim``, a per-head norm on q, on k and on the output) and
``minicpm4`` (``num_attention_heads`` query heads over ``num_key_value_heads``
KV heads, the same q/k norm, an output gate), a SwiGLU of ``intermediate_size``
in every layer. Every kernel is N(0, ``init_std``) in bfloat16 and norm scales
are 1, made on the device from ``--seed`` one tensor at a time. The same call
gives the plain reference its weights.

Disk: as in the module beside this one, layer files repeat as hard links, here
with period ``distinct_layers`` within a kind (the kinds differ in shape). The
decay of a ``lightning-attn`` layer is no tensor: it follows the layer's own
index among all layers, in the program and in the reference alike, so two
layers that share a file still differ.
"""

from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import weights as base

SUFFIX = base.SUFFIX
layer_names = base.layer_names
unflatten = base.unflatten

_OWN = {"init_std", "distinct_layers", "rehearsal", "assumed", "source", "deployment"}
LINEAR = "lightning-attn"


def is_linear_layer(model: dict, i: int) -> bool:
    return model["mixer_types"][i] == LINEAR


def attn_shape(model: dict, linear: bool) -> tuple[int, int, int]:
    """(heads, kv heads, head dim) of a layer kind."""
    if linear:
        return (int(model["lightning_nh"]), int(model["lightning_nkv"]),
                int(model["lightning_head_dim"]))
    return (int(model["num_attention_heads"]), int(model["num_key_value_heads"]),
            int(model["head_dim"]))


def log_decay(model: dict, i: int) -> np.ndarray:
    """float32 [heads]: minus the decay rate of ``lightning-attn`` layer ``i``
    (its index among ALL layers): ``s = 2^(-8 (n + 1) / H) * (1 - i / (L - 1)
    + 1e-5)`` for head n of H, in a model of L layers."""
    h, n = int(model["lightning_nh"]), int(model["num_hidden_layers"])
    slopes = 2.0 ** (-8.0 * (np.arange(h) + 1) / h)
    return (-slopes * (1.0 - i / max(n - 1, 1) + 1e-5)).astype(np.float32)


def slot_of(model: dict, name: str) -> str:
    """The weight slot a layer name draws its tensors from: the layers of one
    kind cycle with period ``distinct_layers`` in their own order; every other
    name is its own slot."""
    if not name.startswith("model.layers."):
        return name
    i = int(name.rsplit(".", 1)[1])
    period = int(model.get("distinct_layers") or 0)
    if not period:
        return f"layer.{i}"
    linear = is_linear_layer(model, i)
    j = sum(is_linear_layer(model, x) == linear for x in range(i))
    return f"{'linear' if linear else 'softmax'}.{j % period}"


def tensor_specs(model: dict, name: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """(native flat key, shape, random?) of one layer file, in a fixed order.
    Kernels are stored [in, out]."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    if name == "model.embed_tokens":
        return [("embedding", (v, d), True)]
    if name == "model.norm":
        return [("scale", (d,), False)]
    if name == "lm_head":
        return [("kernel", (d, v), True)]
    linear = is_linear_layer(model, int(name.rsplit(".", 1)[1]))
    nq, nkv, hd = attn_shape(model, linear)
    f = int(model["intermediate_size"])
    out = [
        ("input_layernorm.scale", (d,), False),
        ("post_attention_layernorm.scale", (d,), False),
        ("attn.wq", (d, nq * hd), True),
        ("attn.wk", (d, nkv * hd), True),
        ("attn.wv", (d, nkv * hd), True),
        ("attn.wo", (nq * hd, d), True),
    ]
    if model.get("qk_norm"):
        out += [("attn.q_norm", (hd,), False), ("attn.k_norm", (hd,), False)]
    if linear and model.get("use_output_norm"):
        out.append(("attn.o_norm", (hd,), False))
    if model.get("use_output_gate" if linear else "attn_use_output_gate"):
        out.append(("attn.wg", (d, nq * hd), True))
    return out + [("mlp.gate", (d, f), True), ("mlp.up", (d, f), True),
                  ("mlp.down", (f, d), True)]


_KINDS = {"model.embed_tokens": 10, "model.norm": 11, "lm_head": 1, "layer": 1000,
          "linear": 2000, "softmax": 3000}


def _slot_id(slot: str) -> int:
    if slot in _KINDS:
        return _KINDS[slot]
    kind, _, idx = slot.rpartition(".")
    return _KINDS[kind] + int(idx)


def layer_tensors(model: dict, seed: int, name: str) -> dict:
    """Device arrays (bf16) of one layer name, from the seed alone."""
    import jax
    import jax.numpy as jnp

    std = float(model.get("init_std", 0.02))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), _slot_id(slot_of(model, name)))
    return {
        k: base._gen(shape, std)(jax.random.fold_in(key, t)) if rand
        else jnp.ones(shape, jnp.bfloat16)
        for t, (k, shape, rand) in enumerate(tensor_specs(model, name))
    }


def hf_config(model: dict) -> dict:
    """The ``config.json`` the program parses: the configuration as run,
    minus the benchmark's own keys, ``mixer_types`` cut to the depth run."""
    cfg = {k: v for k, v in model.items() if k not in _OWN}
    cfg["mixer_types"] = list(model["mixer_types"])[: int(model["num_hidden_layers"])]
    cfg.setdefault("architectures", ["MiniCPMSALAForCausalLM"])
    cfg.setdefault("torch_dtype", "bfloat16")
    return cfg


def write_model(model: dict, seed: int, out_dir: str) -> dict:
    """Write the per-layer files for ``model`` under ``out_dir`` (emptied
    first). Returns {"bytes_written", "bytes_model", "files"}. A copy of
    ``benchmark.weights.write_model``, which is bound to its own module's
    ``slot_of``, ``layer_tensors`` and ``hf_config``."""
    from safetensors.numpy import save_file

    from flexible_llm_sharding_tpu.integrity import manifest as integrity

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    names = layer_names(model)
    first_of: dict[str, str] = {}
    entries: dict[str, dict] = {}
    sizes: dict[str, int] = {}

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
            flat_np = {k: np.ascontiguousarray(np.asarray(a))
                       for k, a in layer_tensors(model, seed, name).items()}
            sizes[name] = sum(a.nbytes for a in flat_np.values())
            futures[name] = pool.submit(store, name, flat_np)
            del flat_np
        for name, fut in futures.items():
            entries[name] = fut.result()
    total = 0
    for name in names:
        src = first_of[slot_of(model, name)]
        total += sizes[src]
        if name != src:
            fn = f"{name}{SUFFIX}"
            os.link(os.path.join(out_dir, f"{src}{SUFFIX}"), os.path.join(out_dir, fn))
            entries[name] = {**entries[src], "file": fn}
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config(model), f)
    with open(os.path.join(out_dir, "fls_tpu_layout.json"), "w") as f:
        json.dump({"layout": "native", "dtype": "bfloat16", "layers": names}, f)
    integrity.write_manifest(out_dir, {n: entries[n] for n in names})
    return {"bytes_written": sum(sizes.values()), "bytes_model": total, "files": len(names)}
