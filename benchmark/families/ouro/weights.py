"""Seeded weights for an ``ouro`` configuration, and the direct writer (the
``deepseek_v3`` module ``benchmark/weights.py`` with this family's tensors;
the generic pieces are imported from it).

One stack of ``num_hidden_layers`` layers, every one of the same shape:
q, k, v, o of ``num_attention_heads`` heads of ``head_dim`` (as many KV heads),
a SwiGLU of ``intermediate_size`` and FOUR norms. Beside the final norm's
scale lives the exit gate, a ``hidden_size -> 1`` kernel and one bias. Every
kernel is N(0, ``init_std``) in bfloat16, norm scales are 1 and the gate's
bias is 0, made on the device from ``--seed`` one tensor at a time. The same
call gives the plain reference its weights.

Tensor names: the program's native per-layer layout, which keeps a layer's
four norms in the slots its sandwich residual reads (``utils/checkpoint.py``
maps the family's published names onto them):

    input_layernorm             <- input_layernorm              N1, attention's input
    post_attention_layernorm    <- input_layernorm_2            N2, attention's output
    pre_feedforward_layernorm   <- post_attention_layernorm     N3, the MLP's input
    post_feedforward_layernorm  <- post_attention_layernorm_2   N4, the MLP's output

Disk: every layer is its own file with its own weights (no ``distinct_layers``
cut, no hard links): 5.34 GB written at the published sizes, what the other
cells write.
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

_OWN = {"init_std", "rehearsal", "assumed", "source", "deployment"}
NORMS = ("input_layernorm", "post_attention_layernorm", "pre_feedforward_layernorm",
         "post_feedforward_layernorm")


def attn_shape(model: dict) -> tuple[int, int, int]:
    """(heads, kv heads, head dim)."""
    nq = int(model["num_attention_heads"])
    return (nq, int(model["num_key_value_heads"]),
            int(model.get("head_dim") or int(model["hidden_size"]) // nq))


def slot_of(model: dict, name: str) -> str:
    """The weight slot a layer name draws its tensors from: its own."""
    return name


def tensor_specs(model: dict, name: str) -> list[tuple[str, tuple[int, ...], str]]:
    """(native flat key, shape, "normal" | "ones" | "zeros") of one layer
    file, in a fixed order. Kernels are stored [in, out]."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    if name == "model.embed_tokens":
        return [("embedding", (v, d), "normal")]
    if name == "model.norm":
        return [("scale", (d,), "ones"), ("gate.kernel", (d, 1), "normal"),
                ("gate.bias", (1,), "zeros")]
    if name == "lm_head":
        return [("kernel", (d, v), "normal")]
    nq, nkv, hd = attn_shape(model)
    f = int(model["intermediate_size"])
    return [(f"{n}.scale", (d,), "ones") for n in NORMS] + [
        ("attn.wq", (d, nq * hd), "normal"),
        ("attn.wk", (d, nkv * hd), "normal"),
        ("attn.wv", (d, nkv * hd), "normal"),
        ("attn.wo", (nq * hd, d), "normal"),
        ("mlp.gate", (d, f), "normal"),
        ("mlp.up", (d, f), "normal"),
        ("mlp.down", (f, d), "normal"),
    ]


def _slot_id(name: str) -> int:
    """A small stable integer per layer name, folded into the key."""
    fixed = {"model.embed_tokens": 10, "model.norm": 11, "lm_head": 1}
    return fixed[name] if name in fixed else 1000 + int(name.rsplit(".", 1)[1])


def layer_tensors(model: dict, seed: int, name: str) -> dict:
    """Device arrays (bf16) of one layer name, from the seed alone."""
    import jax
    import jax.numpy as jnp

    std = float(model.get("init_std", 0.02))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), _slot_id(name))
    fill = {"ones": jnp.ones, "zeros": jnp.zeros}
    return {
        k: base._gen(shape, std)(jax.random.fold_in(key, t)) if how == "normal"
        else fill[how](shape, jnp.bfloat16)
        for t, (k, shape, how) in enumerate(tensor_specs(model, name))
    }


def model_bytes(model: dict) -> int:
    """Bytes of the per-layer files' tensors (bfloat16), by shapes."""
    return sum(2 * int(np.prod(shape)) for n in layer_names(model)
               for _, shape, _ in tensor_specs(model, n))


def hf_config(model: dict) -> dict:
    """The ``config.json`` the program parses: the configuration as run,
    minus the benchmark's own keys, ``layer_types`` cut to the depth run."""
    cfg = {k: v for k, v in model.items() if k not in _OWN}
    if cfg.get("layer_types"):
        cfg["layer_types"] = list(cfg["layer_types"])[: int(model["num_hidden_layers"])]
    cfg.setdefault("architectures", ["OuroForCausalLM"])
    cfg.setdefault("torch_dtype", "bfloat16")
    return cfg


def write_model(model: dict, seed: int, out_dir: str) -> dict:
    """Write the per-layer files for ``model`` under ``out_dir`` (emptied
    first). Returns {"bytes_written", "bytes_model", "files"}; the two byte
    counts are equal: every layer is distinct on disk."""
    from safetensors.numpy import save_file

    from flexible_llm_sharding_tpu.integrity import manifest as integrity

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    names = layer_names(model)

    def store(name: str, flat_np: dict) -> tuple[dict, int]:
        fn = f"{name}{SUFFIX}"
        save_file(flat_np, os.path.join(out_dir, fn))
        return integrity.layer_entry(flat_np, fn), sum(a.nbytes for a in flat_np.values())

    with ThreadPoolExecutor(max_workers=4) as pool:
        futures = {}
        for name in names:
            flat_np = {k: np.ascontiguousarray(np.asarray(a))
                       for k, a in layer_tensors(model, seed, name).items()}
            futures[name] = pool.submit(store, name, flat_np)
            del flat_np
        done = {name: fut.result() for name, fut in futures.items()}
    total = sum(nbytes for _, nbytes in done.values())
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config(model), f)
    with open(os.path.join(out_dir, "fls_tpu_layout.json"), "w") as f:
        json.dump({"layout": "native", "dtype": "bfloat16", "layers": names}, f)
    integrity.write_manifest(out_dir, {n: done[n][0] for n in names})
    return {"bytes_written": total, "bytes_model": total, "files": len(names)}
