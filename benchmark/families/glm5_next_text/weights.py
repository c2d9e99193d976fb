"""Seeded weights for a ``glm5_next_text`` configuration, and the direct writer
(the ``deepseek_v3`` module ``benchmark/weights.py`` with this family's
tensors; the generic pieces are imported from it).

Layer kinds, in the order the configuration's ``layer_types`` and
``mlp_layer_types`` list (read up to the depth run): a ``linear_attention``
(KDA) or a ``deepseek_sparse_attention`` (latent, no rotary part) mixer, a
dense or a sparse (expert) MLP, and one set of mHC weights around each of the
two sublayers. Kernels are N(0, ``init_std``) in bfloat16 and norm scales 1,
made on the device from ``--seed`` one tensor at a time; what N(0, 0.02) would
leave invisible to the comparison is drawn wider, each spread a key of the
configuration file with its reason under ``assumed``: the mHC biases and
scalars (``hc_bias_std``, ``hc_scale``), the decay's rate and bias
(``a_log_std``, ``dt_bias_std``), the convolution's taps (``conv_std``) and
the scale of the MLP's input norm (``mlp_norm_scale``, which puts some SwiGLU
pre-activations past ``swiglu_limit``) and of the latent layers' two inner
norms (``latent_norm_scale``, which sharpens their softmax). The same call gives the plain
reference its weights.

The configuration file states the chip's share of a deployment:
``n_routed_experts`` is the number of experts HELD (``ep_size`` chips share a
layer, this is rank ``ep_rank``), the router keeps ``n_routed_experts *
ep_size`` outputs, ``vocab_size`` is the slice held. ``hf_config`` writes the
program's ``config.json`` with the router's whole width beside ``ep_size`` and
``ep_rank``. The indexer of the sparse layers and the multi-token-prediction
layer have no tensors here (the configuration file says why).

Disk: layer files repeat with period ``distinct_layers`` within a kind (the
kinds differ in shape), as hard links; at most two files wait for the disk
at a time, so that a 2.1 GB layer never queues five deep in host memory.
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

_OWN = {"init_std", "distinct_layers", "rehearsal", "assumed", "source", "published",
        "deployment", "hc_bias_std", "hc_scale", "a_log_std", "dt_bias_std", "conv_std",
        "mlp_norm_scale", "latent_norm_scale"}
_PER_LAYER = ("layer_types", "mlp_layer_types", "indexer_types")
LINEAR = "linear_attention"


def is_linear_layer(model: dict, i: int) -> bool:
    return model["layer_types"][i] == LINEAR


def is_moe_layer(model: dict, i: int) -> bool:
    return bool(model.get("n_routed_experts")) and model["mlp_layer_types"][i] == "sparse"


def router_width(model: dict) -> int:
    return int(model["n_routed_experts"]) * int(model.get("ep_size") or 1)


def held_experts(model: dict) -> range:
    n, rank = int(model["n_routed_experts"]), int(model.get("ep_rank") or 0)
    return range(rank * n, (rank + 1) * n)


def linear_shape(model: dict) -> tuple[int, int, int]:
    """(heads, head dim, convolution taps) of the KDA layers."""
    la = model["linear_attn_config"]
    return int(la["num_heads"]), int(la["head_dim"]), int(la["short_conv_kernel_size"])


def layer_kind(model: dict, i: int) -> str:
    return (("kda" if is_linear_layer(model, i) else "latent")
            + ("_moe" if is_moe_layer(model, i) else "_dense"))


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
    kind = layer_kind(model, i)
    j = sum(layer_kind(model, x) == kind for x in range(i))
    return f"{kind}.{j % period}"


def tensor_specs(model: dict, name: str) -> list[tuple[str, tuple[int, ...], str]]:
    """(native flat key, shape, how it is drawn) of one layer file, in a fixed
    order. Kernels are stored [in, out], the held experts stacked [E held, in,
    out], the convolutions' taps [K, channels] (the last tap on the row
    itself). ``how``: ``normal`` (N(0, init_std)), ``ones``, or the name of
    the configuration key that gives the spread (``hc_scale`` and the
    ``*_norm_scale`` keys: constants)."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    if name == "model.embed_tokens":
        return [("embedding", (v, d), "normal")]
    if name == "model.norm":
        return [("scale", (d,), "ones")]
    if name == "lm_head":
        return [("kernel", (d, v), "normal")]
    i = int(name.rsplit(".", 1)[1])
    n = int(model["hc_mult"])
    mixes = 2 * n + n * n
    out = [("input_layernorm.scale", (d,), "ones"),
           ("post_attention_layernorm.scale", (d,), "mlp_norm_scale")]
    for sub in ("hc_attn", "hc_mlp"):
        out += [(f"{sub}.phi", (n * d, mixes), "normal"), (f"{sub}.b", (mixes,), "hc_bias_std"),
                (f"{sub}.a", (3,), "hc_scale")]
    if is_linear_layer(model, i):
        h, hd, taps = linear_shape(model)
        out += [("attn.wq", (d, h * hd), "normal"), ("attn.wk", (d, h * hd), "normal"),
                ("attn.wv", (d, h * hd), "normal"), ("attn.wo", (h * hd, d), "normal")]
        out += [(f"attn.conv_{c}", (taps, h * hd), "conv_std") for c in "qkv"]
        out += [("attn.f_a", (d, hd), "normal"), ("attn.f_b", (hd, h * hd), "normal"),
                ("attn.A_log", (h,), "a_log_std"), ("attn.dt_bias", (h * hd,), "dt_bias_std"),
                ("attn.wb", (d, h), "normal"), ("attn.o_norm", (hd,), "ones"),
                ("attn.wg_a", (d, hd), "normal"), ("attn.wg_b", (hd, h * hd), "normal")]
    else:
        h, qr, kvr = (int(model[k]) for k in ("num_attention_heads", "q_lora_rank", "kv_lora_rank"))
        dn, dv = int(model["qk_nope_head_dim"]), int(model["v_head_dim"])
        out += [("attn.q_a", (d, qr), "normal"), ("attn.q_a_norm", (qr,), "latent_norm_scale"),
                ("attn.q_b", (qr, h * dn), "normal"), ("attn.kv_a", (d, kvr), "normal"),
                ("attn.kv_a_norm", (kvr,), "latent_norm_scale"), ("attn.kv_b", (kvr, h * (dn + dv)), "normal"),
                ("attn.wo", (h * dv, d), "normal")]
    if is_moe_layer(model, i):
        e, f = int(model["n_routed_experts"]), int(model["moe_intermediate_size"])
        r, fs = router_width(model), f * int(model.get("n_shared_experts") or 0)
        out += [("mlp.router", (d, r), "normal"), ("mlp.correction_bias", (r,), "normal"),
                ("mlp.gate", (e, d, f), "normal"), ("mlp.up", (e, d, f), "normal"),
                ("mlp.down", (e, f, d), "normal")]
        if fs:
            out += [("mlp.shared_gate", (d, fs), "normal"), ("mlp.shared_up", (d, fs), "normal"),
                    ("mlp.shared_down", (fs, d), "normal")]
    else:
        f = int(model["intermediate_size"])
        out += [("mlp.gate", (d, f), "normal"), ("mlp.up", (d, f), "normal"),
                ("mlp.down", (f, d), "normal")]
    return out


_KINDS = {"model.embed_tokens": 10, "model.norm": 11, "lm_head": 1, "layer": 1000,
          "kda_dense": 2000, "kda_moe": 3000, "latent_dense": 4000, "latent_moe": 5000}
_CONSTANT = ("hc_scale", "mlp_norm_scale", "latent_norm_scale")


def _slot_id(slot: str) -> int:
    if slot in _KINDS:
        return _KINDS[slot]
    kind, _, idx = slot.rpartition(".")
    return _KINDS[kind] + int(idx)


def layer_tensors(model: dict, seed: int, name: str) -> dict:
    """Device arrays (bf16) of one layer name, from the seed alone."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed), _slot_id(slot_of(model, name)))
    out = {}
    for t, (k, shape, how) in enumerate(tensor_specs(model, name)):
        if how == "ones":
            out[k] = jnp.ones(shape, jnp.bfloat16)
        elif how in _CONSTANT:
            out[k] = jnp.full(shape, float(model[how]), jnp.bfloat16)
        else:
            std = float(model.get("init_std", 0.02) if how == "normal" else model[how])
            out[k] = base._gen(shape, std)(jax.random.fold_in(key, t))
    return out


def hf_config(model: dict) -> dict:
    """The ``config.json`` the program parses: the configuration as run,
    minus the benchmark's own keys, with the per-layer lists cut to the depth
    run and the router's whole width beside the share held."""
    n = int(model["num_hidden_layers"])
    cfg = {k: v for k, v in model.items() if k not in _OWN}
    for k in _PER_LAYER:
        if k in cfg:
            cfg[k] = list(model[k])[:n]
    cfg["n_routed_experts"] = router_width(model)
    cfg.setdefault("architectures", ["Glm5NextForCausalLM"])
    cfg.setdefault("torch_dtype", "bfloat16")
    return cfg


def write_model(model: dict, seed: int, out_dir: str) -> dict:
    """Write the per-layer files for ``model`` under ``out_dir`` (emptied
    first). Returns {"bytes_written", "bytes_model", "files"}. As
    ``benchmark.weights.write_model``, bound to this module's ``slot_of``,
    ``layer_tensors`` and ``hf_config``, with at most two files in flight."""
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

    with ThreadPoolExecutor(max_workers=2) as pool:
        pending: list[tuple[str, object]] = []
        for name in names:
            slot = slot_of(model, name)
            if slot in first_of:
                continue
            first_of[slot] = name
            while len(pending) >= 2:
                done, fut = pending.pop(0)
                entries[done] = fut.result()
            flat_np = {k: np.ascontiguousarray(np.asarray(a))
                       for k, a in layer_tensors(model, seed, name).items()}
            sizes[name] = sum(a.nbytes for a in flat_np.values())
            pending.append((name, pool.submit(store, name, flat_np)))
            del flat_np
        for done, fut in pending:
            entries[done] = fut.result()
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
