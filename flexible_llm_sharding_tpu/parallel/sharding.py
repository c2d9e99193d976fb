"""Device mesh + sharding rules: how Llama parameters and activations are laid
out over a TPU slice.

The reference has no tensor/sequence parallelism at all — each layer's full
weights go to exactly one device (``/root/reference/utils.py:128-130``) and
"communication" is host-staged tensor copies between Python threads
(``/root/reference/utils.py:166,193-195``). The TPU-native design replaces all
of that with one ``jax.sharding.Mesh`` plus ``NamedSharding`` annotations; XLA
inserts the ICI collectives (all-gather / reduce-scatter / psum) itself.

Mesh axes used across the framework:

- ``dp``  — data parallel: the prompt/batch axis (reference's ``--data_parallel``
  prompt split, ``/root/reference/main.py:67-70``).
- ``tp``  — tensor parallel: attention heads / MLP hidden sharding (Megatron
  layout: column-parallel in-projections, row-parallel out-projections so each
  layer needs exactly one psum, which XLA emits from the sharding annotations).
- ``sp``  — sequence/context parallel: long sequences sharded along length for
  norm/elementwise regions (XLA re-gathers where attention needs full keys).

Parameter layout reminder (models/llama.py): all linear kernels are stored
``[in, out]`` — the transpose of HF — so "column parallel" = shard the LAST
axis, "row parallel" = shard the FIRST axis.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexible_llm_sharding_tpu.config import LlamaConfig

Params = dict[str, Any]


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Join a multi-host (DCN) JAX cluster; returns this process's index.

    The reference tops out at the chips of one host (Python threads in one
    process, ``/root/reference/main.py:59-76``). On TPU pods the same mesh
    code spans hosts: call this once at startup on every host (args usually
    come from the TPU environment automatically), then build meshes from the
    GLOBAL device list — ``make_mesh`` already uses ``jax.devices()``, which
    is cluster-wide after initialization. Lay out mesh axes so the
    fastest-varying (tp/sp) axes stay within a host's ICI domain and only
    dp crosses DCN. No-op when the cluster is already initialized, or when
    auto-detection finds a single-process environment; an EXPLICIT
    coordinator address that fails to connect raises (a silent fallback to
    single-host would duplicate work and corrupt results).
    """
    if jax.distributed.is_initialized():
        return jax.process_index()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        # An explicitly requested cluster must never silently degrade —
        # duplicate single-host runs would race on output files. In
        # auto-detect mode the ONLY benign RuntimeError is the called-after-
        # backend-init guard; a detected cluster that fails to join (e.g.
        # coordinator connect timeout) must raise too, so unmatched messages
        # re-raise — fail-loud if JAX ever rewords the guard.
        if coordinator_address is not None or "before" not in str(e).lower():
            raise
    except ValueError:
        # Auto-detection failed (no cluster env) — fine only if the caller
        # didn't explicitly ask for a cluster.
        if coordinator_address is not None:
            raise
    return jax.process_index()


def make_mesh(
    shape: dict[str, int] | None = None, devices: list | None = None
) -> Mesh:
    """Build a Mesh from axis-name -> size.

    ``shape=None`` gives a 1-D ``('dp',)`` mesh over all visible devices.
    Sizes must multiply to the device count (one axis may be -1 to infer).
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = {"dp": len(devices)}
    names = tuple(shape)
    sizes = list(shape.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devices) // known
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(
            f"mesh shape {dict(zip(names, sizes))} needs {need} devices, "
            f"have {len(devices)}"
        )
    arr = np.asarray(devices[:need]).reshape(sizes)
    return Mesh(arr, names)


def layer_specs(
    tp: str | None = "tp",
    cfg: LlamaConfig | None = None,
    mlp_kind: str | None = None,
) -> Params:
    """PartitionSpecs for one decoder layer's params (Megatron TP layout).

    ``cfg`` adds entries for the bias vectors the model family carries
    (Qwen2 q/k/v, Llama attention_bias/mlp_bias): a column-parallel
    projection's bias shards with its output axis; a row-parallel
    projection's bias is replicated (added once, after the psum).

    ``mlp_kind`` overrides the MLP structure for models that interleave
    structurally different layers (llama4 / qwen3_moe dense interleave):
    ``"dense"`` or ``"moe"``; ``None`` derives it from ``cfg`` (MoE iff the
    config declares experts).
    """
    col = P(None, tp)  # [in, out] sharded on out
    row = P(tp, None)  # [in, out] sharded on in
    rep = P(None)
    bcol = P(tp)  # bias of a column-parallel projection
    if cfg is not None and cfg.kv_lora_rank:
        # MLA (deepseek_v3): the LoRA down-projections (q_a, kv_a) and
        # their norms are replicated — kv_a's output carries the shared
        # rope key every head needs, and both are tiny (rank x D). The
        # per-head up-projections (q_b / kv_b / dense wq) column-shard by
        # head like Megatron q/k/v; wo row-shards over the heads' values.
        attn: Params = {
            "kv_a": rep, "kv_a_norm": rep, "kv_b": col, "wo": row,
        }
        if cfg.q_lora_rank:
            attn |= {"q_a": rep, "q_a_norm": rep, "q_b": col}
        else:
            attn["wq"] = col
        if cfg.attention_in_bias:
            # Biases exist on the LoRA down-projections only (HF's dense
            # q_proj is bias=False unconditionally); they act on
            # replicated outputs.
            attn["bkv_a"] = rep
            if cfg.q_lora_rank:
                attn["bq_a"] = rep
    else:
        attn = {"wq": col, "wk": col, "wv": col, "wo": row}
    if mlp_kind is None:
        mlp_kind = "moe" if (cfg is not None and cfg.num_local_experts) else "dense"
    if mlp_kind == "moe":
        # Expert parallelism: the stacked [E, ...] expert arrays shard on the
        # expert axis — each chip computes its own experts for all tokens and
        # GSPMD inserts one psum for the routed combine (models/llama.py
        # _moe_mlp). Router stays replicated (it is [D, E], tiny).
        exp = P(tp, None, None)
        mlp: Params = {"router": rep, "gate": exp, "up": exp, "down": exp}
        if cfg is not None and cfg.model_type in ("llama4_text", "deepseek_v3"):
            # The always-on shared expert (llama4 / deepseek) is a plain
            # Megatron MLP alongside the expert-sharded routed stack; its
            # row-parallel down-projection folds into the same psum.
            mlp |= {"shared_gate": col, "shared_up": col, "shared_down": row}
        if cfg is not None and cfg.model_type == "deepseek_v3":
            mlp["correction_bias"] = rep  # [E] routing buffer, tiny
    else:
        mlp = {"gate": col, "up": col, "down": row}
    if cfg is not None:
        if cfg.attention_in_bias and not cfg.kv_lora_rank:
            attn |= {"bq": bcol, "bk": bcol, "bv": bcol}
        if cfg.attention_out_bias:
            attn["bo"] = rep
        if cfg.qk_norm:
            attn |= {"q_norm": rep, "k_norm": rep}  # [head_dim], tiny
        if cfg.mlp_bias and not cfg.num_local_experts:
            mlp |= {"bgate": bcol, "bup": bcol, "bdown": rep}
    out = {
        "input_layernorm": {"scale": rep},
        "post_attention_layernorm": {"scale": rep},
        "attn": attn,
        "mlp": mlp,
    }
    if cfg is not None and cfg.ffw_sandwich_norms:
        out["pre_feedforward_layernorm"] = {"scale": rep}
        out["post_feedforward_layernorm"] = {"scale": rep}
    return out


def param_specs(
    cfg: LlamaConfig,
    tp: str | None = "tp",
    stacked: bool = False,
    pp: str | None = None,
) -> Params:
    """PartitionSpec pytree matching ``llama.init_params`` layout.

    ``stacked=True`` means ``params['layers']`` is one pytree with a leading
    [num_layers] axis (the scan layout); ``pp`` optionally shards that layer
    axis across a pipeline mesh axis.
    """
    lspec = layer_specs(tp, cfg)
    if stacked:
        layers = jax.tree.map(
            lambda s: P(pp, *s), lspec, is_leaf=lambda x: isinstance(x, P)
        )
    else:
        layers = [lspec] * cfg.num_hidden_layers
    specs: Params = {
        "embed": {"embedding": P(None, tp)},  # [V, D] sharded on hidden
        "layers": layers,
        "norm": {"scale": P(None)},
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = {"kernel": P(None, tp)}  # [D, V] sharded on vocab
    return specs


def data_spec(dp: str | None = "dp", sp: str | None = None) -> P:
    """Token ids [B, L]: batch over dp, optionally sequence over sp."""
    return P(dp, sp)


class TpPlacement:
    """Weight/activation placement for tensor-parallel streaming inference.

    The reference never splits a layer across devices (each layer's full
    weights land on one GPU, ``/root/reference/utils.py:128-130``); on TPU the
    idiomatic alternative is Megatron-style sharding over a ``tp`` mesh axis:
    every streamed shard's matmuls are column/row-partitioned across the
    chips (``layer_specs``), activations stay replicated, and XLA inserts the
    ICI all-reduces where the row-parallel products need them. Per-chip
    weight HBM drops by the tp factor — multiplying with the streaming
    design's own layer_num_per_shard reduction — and the matmuls ride all
    chips' MXUs at once.

    Duck-types as the executor's ``device``: ``segment_target(kind)`` gives
    the ``jax.device_put`` target for one weight segment, ``act`` the target
    for activations. The jitted block programs need no changes — GSPMD
    partitions them from the argument shardings.
    """

    def __init__(self, devices: Sequence, cfg: LlamaConfig | None = None):
        if len(devices) < 2:
            raise ValueError("TpPlacement needs >= 2 devices")
        self.mesh = make_mesh({"tp": len(devices)}, list(devices))
        self.act = NamedSharding(self.mesh, P())

        def decoder_tree(mlp_kind: str | None):
            # Stacked-scan decoder pytrees carry a leading [k] layer axis.
            return jax.tree.map(
                lambda s: NamedSharding(self.mesh, P(None, *s)),
                layer_specs("tp", cfg, mlp_kind=mlp_kind),
                is_leaf=lambda x: isinstance(x, P),
            )

        self._decoder = decoder_tree(None)
        # Mixed dense/MoE stacks (llama4, qwen3_moe dense interleave) produce
        # structurally different "decoders" segments — the loader splits them
        # into homogeneous scan runs, and segment_target picks the matching
        # spec tree per run by the host structure.
        self._decoder_dense = (
            decoder_tree("dense")
            if cfg is not None and cfg.num_local_experts and cfg.moe_layer_pattern
            else self._decoder
        )
        self._by_kind = {
            "decoders": {
                "layers": self._decoder,
                "sliding": self.act
                if cfg is not None and cfg.layer_sliding is not None
                else None,
                "rope": self.act
                if cfg is not None and cfg.layer_rope is not None
                else None,
            },
            # Embed/norm are small and read row-wise per token id; replicate.
            "embed": self.act,
            "norm": self.act,
            # Head kernel [D, V] column-sharded: each chip scores a vocab
            # slice; the softmax's global max/sum become ICI all-reduces.
            "head": {"kernel": NamedSharding(self.mesh, P(None, "tp"))},
        }

    def segment_target(self, kind: str, host=None):
        """Sharding target for one streamed segment. ``host`` (the host-side
        pytree about to be device_put) disambiguates mixed dense/MoE models:
        a decoder run without a router takes the dense Megatron specs."""
        target = self._by_kind[kind]
        if (
            kind == "decoders"
            and host is not None
            and "router" not in host["layers"]["mlp"]
        ):
            target = dict(target, layers=self._decoder_dense)
        return target

    def check(self, cfg: LlamaConfig) -> None:
        check_tp_divisibility(cfg, self.mesh.shape["tp"])


def check_tp_divisibility(cfg: LlamaConfig, tp_size: int) -> None:
    """TP constraints — fail loudly before XLA produces a cryptic error."""
    cfg.require_one_attention_shape("tensor parallelism")
    cfg.require_single_visit("tensor parallelism")
    if cfg.num_attention_heads % tp_size:
        raise ValueError(
            f"num_attention_heads={cfg.num_attention_heads} not divisible by tp={tp_size}"
        )
    if cfg.num_key_value_heads % tp_size:
        raise ValueError(
            f"num_key_value_heads={cfg.num_key_value_heads} not divisible by tp={tp_size}"
        )
    if cfg.num_local_experts:
        # MoE MLPs shard on the expert axis, not the hidden axis.
        if cfg.num_local_experts % tp_size:
            raise ValueError(
                f"num_local_experts={cfg.num_local_experts} not divisible by tp={tp_size}"
            )
        # Dense interleave layers (llama4 intermediate_size_mlp, qwen3_moe
        # mlp_only_layers) and llama4's shared expert shard on their own
        # hidden axis like any Megatron MLP.
        dense_f = cfg.intermediate_size_mlp or (
            cfg.intermediate_size if cfg.moe_layer_pattern else None
        )
        if (
            cfg.model_type in ("llama4_text", "deepseek_v3")
            and cfg.intermediate_size % tp_size
        ):
            raise ValueError(
                f"shared-expert intermediate_size={cfg.intermediate_size} "
                f"not divisible by tp={tp_size}"
            )
        if dense_f and dense_f % tp_size:
            raise ValueError(
                f"dense-layer intermediate size {dense_f} not divisible by tp={tp_size}"
            )
    elif cfg.intermediate_size % tp_size:
        raise ValueError(
            f"intermediate_size={cfg.intermediate_size} not divisible by tp={tp_size}"
        )


def tree_shardings(mesh: Mesh, specs: Params) -> Params:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def shard_params(params: Params, mesh: Mesh, specs: Params) -> Params:
    """device_put a (host or device) param pytree onto the mesh per specs."""
    return jax.device_put(params, tree_shardings(mesh, specs))


__all__ = [
    "initialize_multihost",
    "make_mesh",
    "param_specs",
    "layer_specs",
    "data_spec",
    "TpPlacement",
    "check_tp_divisibility",
    "tree_shardings",
    "shard_params",
]
