"""Seeded weights for a ``mimo_v2_flash`` configuration, and the direct writer
(the ``deepseek_v3`` module ``benchmark/weights.py`` with this family's
tensors; the generic pieces are imported from it).

Every kernel is N(0, ``init_std``) in bfloat16 and norm scales are 1, made on
the device from ``--seed`` one tensor at a time; the window layers' sink
logits are N(``sink_mean``, ``sink_std``) (the configuration file says why).
The same call gives the plain reference its weights.

The configuration file states the chip's share of a deployment:
``n_routed_experts`` is the number of experts HELD (``ep_size`` chips share a
layer, this is rank ``ep_rank``), the router keeps ``n_routed_experts *
ep_size`` outputs, ``vocab_size`` is the slice held. ``hf_config`` writes the
program's ``config.json`` with the router's whole width beside ``ep_size`` and
``ep_rank``, which is how the program learns what it holds.

Disk: as in the module beside this one, expert layers repeat with period
``distinct_expert_layers`` as hard links, here within each attention kind
(a window layer's file can stand for another window layer only: the kinds
differ in shape).
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

_OWN = {"init_std", "sink_mean", "sink_std", "distinct_expert_layers", "rehearsal",
        "assumed", "source", "published", "deployment"}
_PER_LAYER = ("hybrid_layer_pattern", "moe_layer_freq")


def is_window_layer(model: dict, i: int) -> bool:
    return bool(model["hybrid_layer_pattern"][i])


def is_moe_layer(model: dict, i: int) -> bool:
    return bool(model.get("n_routed_experts")) and bool(model["moe_layer_freq"][i])


def router_width(model: dict) -> int:
    return int(model["n_routed_experts"]) * int(model.get("ep_size") or 1)


def held_experts(model: dict) -> range:
    n, rank = int(model["n_routed_experts"]), int(model.get("ep_rank") or 0)
    return range(rank * n, (rank + 1) * n)


def attn_shape(model: dict, window: bool) -> tuple[int, int, int, int]:
    """(heads, kv heads, qk dim, v dim) of a layer kind."""
    pre = "swa_" if window else ""
    return (int(model[pre + "num_attention_heads"]), int(model[pre + "num_key_value_heads"]),
            int(model[pre + "head_dim"]), int(model[pre + "v_head_dim"]))


def layer_kind(model: dict, i: int) -> str:
    return (("window" if is_window_layer(model, i) else "full")
            + ("_moe" if is_moe_layer(model, i) else "_dense"))


def slot_of(model: dict, name: str) -> str:
    """The weight slot a layer name draws its tensors from: expert layers of
    one attention kind cycle with period ``distinct_expert_layers`` in their
    own order; every other name is its own slot."""
    if not name.startswith("model.layers."):
        return name
    i = int(name.rsplit(".", 1)[1])
    period = int(model.get("distinct_expert_layers") or 0)
    if not is_moe_layer(model, i) or not period:
        return f"layer.{i}"
    kind = layer_kind(model, i)
    j = sum(layer_kind(model, x) == kind for x in range(i))
    return f"{kind}.{j % period}"


def tensor_specs(model: dict, name: str) -> list[tuple[str, tuple[int, ...], str]]:
    """(native flat key, shape, how it is drawn: ``normal`` / ``ones`` /
    ``sink``) of one layer file, in a fixed order. Kernels are stored
    [in, out], the held experts stacked [E held, in, out]."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    if name == "model.embed_tokens":
        return [("embedding", (v, d), "normal")]
    if name == "model.norm":
        return [("scale", (d,), "ones")]
    if name == "lm_head":
        return [("kernel", (d, v), "normal")]
    i = int(name.rsplit(".", 1)[1])
    window = is_window_layer(model, i)
    nq, nkv, hd, vd = attn_shape(model, window)
    out = [
        ("input_layernorm.scale", (d,), "ones"),
        ("post_attention_layernorm.scale", (d,), "ones"),
        ("attn.wq", (d, nq * hd), "normal"),
        ("attn.wk", (d, nkv * hd), "normal"),
        ("attn.wv", (d, nkv * vd), "normal"),
        ("attn.wo", (nq * vd, d), "normal"),
    ]
    if model.get("add_swa_attention_sink_bias" if window else "add_full_attention_sink_bias"):
        out.append(("attn.sink", (nq,), "sink"))
    if is_moe_layer(model, i):
        e, f = int(model["n_routed_experts"]), int(model["moe_intermediate_size"])
        r = router_width(model)
        out += [
            ("mlp.router", (d, r), "normal"),
            ("mlp.correction_bias", (r,), "normal"),
            ("mlp.gate", (e, d, f), "normal"),
            ("mlp.up", (e, d, f), "normal"),
            ("mlp.down", (e, f, d), "normal"),
        ]
    else:
        f = int(model["intermediate_size"])
        out += [("mlp.gate", (d, f), "normal"), ("mlp.up", (d, f), "normal"),
                ("mlp.down", (f, d), "normal")]
    return out


_KINDS = {"model.embed_tokens": 10, "model.norm": 11, "lm_head": 1, "layer": 1000,
          "window_moe": 2000, "full_moe": 3000, "window_dense": 4000, "full_dense": 5000}


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
    out = {}
    for t, (k, shape, how) in enumerate(tensor_specs(model, name)):
        if how == "ones":
            out[k] = jnp.ones(shape, jnp.bfloat16)
            continue
        a = base._gen(shape, 1.0 if how == "sink" else std)(jax.random.fold_in(key, t))
        if how == "sink":
            a = (a.astype(jnp.float32) * float(model.get("sink_std", 1.0))
                 + float(model.get("sink_mean", 0.0))).astype(jnp.bfloat16)
        out[k] = a
    return out


def hf_config(model: dict) -> dict:
    """The ``config.json`` the program parses: the configuration as run,
    minus the benchmark's own keys, with the per-layer lists cut to the depth
    run and the router's whole width beside the share held."""
    n = int(model["num_hidden_layers"])
    cfg = {k: v for k, v in model.items() if k not in _OWN}
    for k in _PER_LAYER:
        cfg[k] = list(model[k])[:n]
    cfg["n_routed_experts"] = router_width(model)
    cfg.setdefault("architectures", ["MiMoV2FlashForCausalLM"])
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
