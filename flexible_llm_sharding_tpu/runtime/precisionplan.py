"""Per-layer mixed-precision planning: spend bf16 only where it matters.

The architecture's defining cost is that every decode sweep streams the
whole model over the host->HBM link (PAPER.md §0), so bytes-per-sweep
converts almost directly into tokens/sec. The repo already ships UNIFORM
int8/int4 checkpoints with on-device dequant — but quality sensitivity is
not uniform across layers (LLM.int8() / AWQ: a small set of salient
layers dominates degradation), so a per-layer dtype choice buys most of
int4's bandwidth at near-bf16 quality.

Three pieces live here:

- :func:`probe_sensitivity` — the measurement. For each layer and each
  candidate dtype, swap JUST that layer to a quantize->dequantize
  simulation of the dtype (the exact rounding ``requantize_native`` will
  materialize, via ``checkpoint.simulate_quantized``) and score the KL
  divergence of the next-token distribution against the bf16 oracle on a
  small calibration batch. Deterministic: no RNG, no wall clock — the
  same calibration batch always yields the same table.
- :func:`plan_from_sensitivity` — the greedy optimizer. Budget mode
  starts every layer at bf16 and downgrades the cheapest-divergence-per-
  byte-saved steps until the estimated bytes/sweep fit; cap mode starts
  every layer at int4 and upgrades the biggest-divergence-relief-per-
  byte steps until the estimated total divergence fits. Ties break by
  layer index, so plans are reproducible bit-for-bit.
- :class:`PrecisionPlan` — the serializable artifact
  (``precision_plan.json``), embedded in the materialized checkpoint dir
  by ``checkpoint.requantize_native(plan=...)`` so the streaming stack,
  the residency planner, and the ``verify`` CLI audit all read the SAME
  layer->dtype mapping the converter wrote.

The probe holds the whole (calibration-scale) model in host RAM and runs
monolithic forwards — it is an OFFLINE calibration tool for the same
small-model regime the tests' oracles use, not a streaming path. For
very large models, probe a truncated proxy or raise the calibration
host's RAM; the plan file it emits is size-independent.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any, Mapping, Sequence

import numpy as np

from flexible_llm_sharding_tpu.utils import checkpoint

PLAN_NAME = "precision_plan.json"

# The dtype ladder, cheapest first. "bf16" is the lossless reference
# (zero divergence by definition — it IS the oracle's storage dtype).
PLAN_DTYPES = ("int4", "int8", "bf16")

# Plan dtype -> the concrete on-file dtype kinds the integrity manifest
# may record for it (checkpoint.flat_dtype_kind). int4 checkpoints may
# carry per-tensor int8 fallbacks (in-dim off the quant group) and a
# layer with NO quantizable tensors (model.norm: 1-D scales only) stays
# exact float32 under either quantizer — leaves self-describe, so those
# kinds are legitimate sub-kinds, not mismatches.
PLAN_KIND_ACCEPTS = {
    "bf16": ("bfloat16", "none"),
    "int8": ("int8", "float32", "none"),
    "int4": ("int4", "int8", "float32", "none"),
}


@dataclasses.dataclass(frozen=True)
class PrecisionPlan:
    """A layer->dtype assignment plus the evidence it was planned from.

    ``layers`` is execution-ordered ``(layer_name, dtype)`` with dtype in
    :data:`PLAN_DTYPES`. ``divergence_cap`` is the plan's DECLARED cap on
    end-to-end next-token KL vs the bf16 oracle: the user's cap in cap
    mode, or the calibration-measured divergence with headroom in budget
    mode — the tests' end-to-end check (a mixed stream's scores against
    the bf16 stream's) holds the run to this declared number."""

    layers: tuple[tuple[str, str], ...]
    divergence_cap: float
    bytes_budget: int | None = None
    est_bytes: int = 0
    baseline_bytes: int = 0
    est_divergence: float = 0.0
    measured_divergence: float | None = None
    calibration_prompts: int = 0

    def __post_init__(self) -> None:
        for name, dt in self.layers:
            if dt not in PLAN_DTYPES:
                raise ValueError(
                    f"PrecisionPlan: layer {name!r} has dtype {dt!r}; "
                    f"must be one of {PLAN_DTYPES}"
                )

    @functools.cached_property
    def dtypes(self) -> dict[str, str]:
        """layer -> dtype lookup dict, built once (cached_property writes
        the instance __dict__ directly, which a frozen dataclass allows).
        Treat as read-only — it is a cache of ``layers``, not state."""
        return dict(self.layers)

    def dtype_for(self, layer_name: str) -> str:
        try:
            return self.dtypes[layer_name]
        except KeyError:
            raise KeyError(
                f"PrecisionPlan has no entry for layer {layer_name!r} — "
                "the plan must cover every layer of the checkpoint it is "
                "applied to"
            ) from None

    @property
    def bytes_saved_frac(self) -> float:
        """Estimated fraction of the uniform-bf16 sweep bytes the plan
        removes from the link."""
        if not self.baseline_bytes:
            return 0.0
        return 1.0 - self.est_bytes / self.baseline_bytes

    def counts(self) -> dict[str, int]:
        out = {d: 0 for d in PLAN_DTYPES}
        for _, dt in self.layers:
            out[dt] += 1
        return out

    # -- serialization -----------------------------------------------------

    def to_json(self) -> dict:
        return {
            "version": 1,
            "layers": {name: dt for name, dt in self.layers},
            "layer_order": [name for name, _ in self.layers],
            "divergence_cap": self.divergence_cap,
            "bytes_budget": self.bytes_budget,
            "est_bytes": self.est_bytes,
            "baseline_bytes": self.baseline_bytes,
            "est_divergence": self.est_divergence,
            "measured_divergence": self.measured_divergence,
            "calibration_prompts": self.calibration_prompts,
        }

    @classmethod
    def from_json(cls, data: Mapping[str, Any]) -> "PrecisionPlan":
        layer_map = data["layers"]
        order = data.get("layer_order") or sorted(layer_map)
        return cls(
            layers=tuple((n, layer_map[n]) for n in order),
            divergence_cap=float(data["divergence_cap"]),
            bytes_budget=(
                int(data["bytes_budget"])
                if data.get("bytes_budget") is not None
                else None
            ),
            est_bytes=int(data.get("est_bytes", 0)),
            baseline_bytes=int(data.get("baseline_bytes", 0)),
            est_divergence=float(data.get("est_divergence", 0.0)),
            measured_divergence=(
                float(data["measured_divergence"])
                if data.get("measured_divergence") is not None
                else None
            ),
            calibration_prompts=int(data.get("calibration_prompts", 0)),
        )

    def write(self, path: str) -> str:
        """Atomically write the plan JSON to ``path`` (tmp + rename, the
        manifest convention) — the ONE serialization used for both the
        embedded plan and standalone plan files."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, sort_keys=True, indent=1)
        os.replace(tmp, path)
        return path

    def save(self, model_dir: str) -> str:
        """Embed the plan in a checkpoint dir as ``precision_plan.json``."""
        return self.write(os.path.join(model_dir, PLAN_NAME))

    @classmethod
    def load(cls, model_dir: str) -> "PrecisionPlan | None":
        """The plan embedded in a checkpoint dir, or None when the dir is
        a uniform-precision checkpoint (no plan file). A corrupt plan
        raises ValueError, and an existing-but-unreadable one (EACCES,
        EIO) propagates its OSError — a plan that EXISTS but cannot be
        checked must never silently read as "uniform checkpoint", which
        would skip every plan-level audit."""
        path = os.path.join(model_dir, PLAN_NAME)
        try:
            with open(path) as f:
                raw = f.read()
        except FileNotFoundError:
            return None
        try:
            return cls.from_json(json.loads(raw))
        except (ValueError, KeyError, TypeError) as e:
            raise ValueError(
                f"{path}: corrupt precision plan ({e!r}); re-materialize "
                "the checkpoint or delete the plan file"
            ) from e


def plan_manifest_problems(
    plan: "PrecisionPlan", manifest: Mapping[str, Any] | None
) -> list[tuple[str, str]]:
    """Plan-vs-manifest disagreements as ``[(layer, description)]`` —
    the ONE comparison shared by the load path
    (``executor._check_precision_plan`` raises ``PrecisionMismatch`` on
    the first) and the offline ``verify`` audit (reports them all), so
    the two consumers can never drift on what "matches the plan" means.
    Manifest entries without a recorded dtype (pre-dtype manifests) are
    not problems — back-compat."""
    problems: list[tuple[str, str]] = []
    layers = (manifest or {}).get("layers", {})
    for name, plan_dtype in plan.layers:
        entry = layers.get(name)
        if entry is None:
            problems.append(
                (
                    name,
                    f"precision plan covers layer {name!r} but the "
                    "integrity manifest has no entry for it — plan and "
                    "checkpoint drifted (re-materialize with "
                    "requantize_native(plan=...))",
                )
            )
            continue
        kind = entry.get("dtype")
        if kind is not None and kind not in PLAN_KIND_ACCEPTS[plan_dtype]:
            problems.append(
                (
                    name,
                    f"layer {name!r} is planned {plan_dtype!r} but the "
                    f"integrity manifest records stored kind {kind!r}",
                )
            )
    return problems


# ---------------------------------------------------------------------------
# Byte estimation (shapes-only, no quantization pass)
# ---------------------------------------------------------------------------


def _leaf_arrays(tree) -> list[np.ndarray]:
    import jax

    return [a for a in jax.tree.leaves(tree) if hasattr(a, "shape")]


def _is_float(a) -> bool:
    return checkpoint.is_float_like(a)


def _quantizable(a) -> bool:
    """Mirrors ``checkpoint._quantize_flat``: matmul kernels (>= 2-D
    floats) quantize; 1-D tensors stay exact float32."""
    return np.ndim(a) >= 2 and _is_float(a)


def layer_dtype_bytes(tree) -> dict[str, int]:
    """Streamed bytes one layer's host tree would cost per plan dtype —
    the same packed (q + scales) sizes ``checkpoint._quantize_flat``
    materializes, computed from shapes alone. The planner's byte
    estimates therefore match the converter's output exactly (asserted
    in tests), never the dequantized logical size."""
    out = {d: 0 for d in PLAN_DTYPES}
    for a in _leaf_arrays(tree):
        shape = tuple(np.shape(a))
        elems = int(np.prod(shape)) if shape else 1
        if not _is_float(a):
            for d in PLAN_DTYPES:
                out[d] += int(np.asarray(a).nbytes)
            continue
        if not _quantizable(a):
            # 1-D float tensors: bf16 casts them (split_into_layers'
            # uniform cast rule); the quantizers keep them exact at
            # float32 (sub-fp32 sources up-cast; fp64 passes through).
            itemsize = max(np.asarray(a).dtype.itemsize, 4)
            out["bf16"] += elems * 2
            out["int8"] += elems * itemsize
            out["int4"] += elems * itemsize
            continue
        *lead, n_in, n_out = shape
        lead_n = int(np.prod(lead)) if lead else 1
        out["bf16"] += elems * 2
        # int8: per-output-channel — q int8 + fp32 scale [lead..., out].
        out["int8"] += elems + lead_n * n_out * 4
        if n_in % checkpoint.INT4_GROUP == 0:
            # int4: packed nibbles + fp32 group scales [.., in/g, out].
            out["int4"] += elems // 2 + (
                lead_n * (n_in // checkpoint.INT4_GROUP) * n_out * 4
            )
        else:
            # Off-group in-dim falls back to per-channel int8 for that
            # tensor (checkpoint._quantize_flat's rule).
            out["int4"] += elems + lead_n * n_out * 4
    return out


# ---------------------------------------------------------------------------
# Sensitivity probe
# ---------------------------------------------------------------------------


def _load_float_params(model_path: str, layer_names):
    """Host params pytree of a FLOAT native checkpoint dir, at its
    ORIGINAL stored values — the probe simulates every candidate dtype
    from exactly these (the converter quantizes the source values, so
    simulating from anything else would measure different rounding).
    The bf16 ORACLE network is derived from this via
    ``simulate_layer(tree, "bf16")`` per layer."""
    if checkpoint._BFLOAT16 is None:  # pragma: no cover - ml_dtypes ships
        raise ImportError("mixed-precision planning requires ml_dtypes")
    params: dict[str, Any] = {"layers": []}
    for name in layer_names:
        tree = checkpoint.load_layer(model_path, name)
        if any(
            checkpoint.is_quantized_leaf(leaf)
            for leaf in _leaf_arrays_grouped(tree)
        ):
            raise ValueError(
                f"{model_path}/{name}: already quantized — probe and plan "
                "from the original float checkpoint (requantize_native's "
                "rule)"
            )
        if name == "model.embed_tokens":
            params["embed"] = tree
        elif name == "model.norm":
            params["norm"] = tree
        elif name == "lm_head":
            params["lm_head"] = tree
        else:
            params["layers"].append(tree)
    return params


def _leaf_arrays_grouped(tree):
    import jax

    return jax.tree.leaves(
        jax.tree.map(
            lambda n: n, tree, is_leaf=checkpoint.is_quantized_leaf
        ),
        is_leaf=checkpoint.is_quantized_leaf,
    )


def simulate_layer(tree, dtype: str):
    """One layer's ORIGINAL-value tree re-expressed at ``dtype`` (float32
    out) — exactly the values the streaming executor computes after
    ``requantize_native`` materialized the dtype from the same source
    and ``_dequant_tree``/``_cast_tree`` expanded it on device:
    quantizable kernels take the quantize->dequantize round trip (int8/
    int4, fallback rule included) or the bf16 cast round trip; 1-D
    floats stay exact under the quantizers and bf16-round under 'bf16'
    (``_cast_flat_bf16``'s uniform rule)."""
    import jax

    if dtype == "bf16" and checkpoint._BFLOAT16 is None:  # pragma: no cover
        raise ImportError("dtype='bf16' simulation requires ml_dtypes")

    def one(a):
        a = np.asarray(a)
        if not _is_float(a):
            return a
        if dtype == "bf16":
            return np.asarray(
                np.asarray(a, checkpoint._BFLOAT16), np.float32
            )
        if not _quantizable(a):
            return a.astype(np.float32)
        return checkpoint.simulate_quantized(a, dtype)

    return jax.tree.map(one, tree)


def _calibration_rows(prompts, tokenizer) -> list[np.ndarray]:
    """Full prefix+suffix token rows for every (prompt, suffix) pair —
    the same sequences the repo's oracle checks score."""
    from flexible_llm_sharding_tpu.runtime.tokenization import PromptTokenizer

    tok = PromptTokenizer(tokenizer, bucket_multiple=8)
    rows = []
    for prefix, suffixes in prompts:
        t = tok(prefix, suffixes)
        for s in range(t.num_suffixes):
            n_real = int(t.suffix_eos[s]) + 1
            rows.append(
                np.concatenate(
                    [t.prefix_ids[: t.prefix_len], t.suffix_ids[s, :n_real]]
                )
            )
    return rows


def _next_token_probs(params_dev, model_cfg, rows) -> np.ndarray:
    """[n_rows, V] float32 next-token distributions (softmax of the last
    position), the quantity scoring mode exists to produce. ``params_dev``
    is an ALREADY device-converted pytree (the probe converts once and
    swaps single layers, instead of re-uploading the whole model per
    forward). Rows of equal length batch into one forward — batching is
    what keeps the probe an offline tool, not an overnight job."""
    import jax
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.models import llama

    by_len: dict[int, list[int]] = {}
    for i, row in enumerate(rows):
        by_len.setdefault(len(row), []).append(i)
    out: list[np.ndarray | None] = [None] * len(rows)
    for idxs in by_len.values():
        batch = jnp.asarray(np.stack([rows[i] for i in idxs]))
        logits = llama.forward_full(params_dev, model_cfg, batch)
        probs = np.asarray(jax.nn.softmax(logits[:, -1], axis=-1), np.float32)
        for j, i in enumerate(idxs):
            out[i] = probs[j]
    return np.stack(out)


def kl_divergence(p: np.ndarray, q: np.ndarray) -> float:
    """Mean KL(p || q) over rows, numerically floored — the probe's and
    the tests' ONE divergence definition."""
    p = np.clip(np.asarray(p, np.float64), 1e-12, None)
    q = np.clip(np.asarray(q, np.float64), 1e-12, None)
    p = p / p.sum(axis=-1, keepdims=True)
    q = q / q.sum(axis=-1, keepdims=True)
    return float(np.mean(np.sum(p * (np.log(p) - np.log(q)), axis=-1)))


@dataclasses.dataclass
class _ProbeContext:
    """Everything one calibration session shares — model loaded once,
    converted to device arrays once, oracle computed once. ``build_plan``
    reuses it across the probe, the byte estimates, and the end-to-end
    validation instead of re-reading the checkpoint per stage.

    ``params`` holds the ORIGINAL stored values (what every dtype
    simulates from — the converter's own source); ``params_dev`` is the
    device-resident bf16-ORACLE network (every layer at
    ``simulate_layer(raw, "bf16")``), the baseline candidate layers swap
    into."""

    model_cfg: Any
    layer_names: list[str]
    params: dict  # host pytree, original values
    params_dev: dict  # bf16-oracle pytree on device, shared per forward
    rows: list
    oracle: np.ndarray

    def host_tree(self, name: str):
        holder, key = self._slot(self.params, name)
        return holder[key]

    def swapped_dev(self, sims: Mapping[str, Any]) -> dict:
        """params_dev with the layers in ``sims`` replaced (device-
        converted) — shallow copies, every untouched layer stays the
        same resident array."""
        import jax
        import jax.numpy as jnp

        out = dict(self.params_dev)
        out["layers"] = list(self.params_dev["layers"])
        for name, sim in sims.items():
            holder, key = self._slot(out, name)
            holder[key] = jax.tree.map(jnp.asarray, sim)
        return out

    @staticmethod
    def _slot(params, name: str):
        # Tied checkpoints' phantom lm_head never reaches here:
        # layer_names_for(tied=True) omits it (the streamed head is
        # requantized from the embedding at stream time — executor's
        # rule, not this plan's to choose).
        if name == "model.embed_tokens":
            return params, "embed"
        if name == "model.norm":
            return params, "norm"
        if name == "lm_head":
            return params, "lm_head"
        return params["layers"], int(name.split(".")[2])


def _probe_context(model_path: str, prompts, tokenizer) -> _ProbeContext:
    import jax
    import jax.numpy as jnp

    from flexible_llm_sharding_tpu.config import LlamaConfig

    model_cfg = LlamaConfig.from_pretrained(model_path)
    layer_names = checkpoint.layer_names_for(
        model_cfg.num_hidden_layers, model_cfg.tie_word_embeddings
    )
    params = _load_float_params(model_path, layer_names)
    oracle_host = {
        "embed": simulate_layer(params["embed"], "bf16"),
        "layers": [
            simulate_layer(t, "bf16") for t in params["layers"]
        ],
        "norm": simulate_layer(params["norm"], "bf16"),
    }
    if "lm_head" in params:
        oracle_host["lm_head"] = simulate_layer(params["lm_head"], "bf16")
    params_dev = jax.tree.map(jnp.asarray, oracle_host)
    rows = _calibration_rows(prompts, tokenizer)
    oracle = _next_token_probs(params_dev, model_cfg, rows)
    return _ProbeContext(
        model_cfg=model_cfg,
        layer_names=list(layer_names),
        params=params,
        params_dev=params_dev,
        rows=rows,
        oracle=oracle,
    )


def _probe_table(
    ctx: _ProbeContext, candidates: Sequence[str]
) -> dict[str, dict[str, float]]:
    table: dict[str, dict[str, float]] = {}
    for name in ctx.layer_names:
        original = ctx.host_tree(name)
        if not any(_quantizable(a) for a in _leaf_arrays(original)):
            # Nothing quantizable (model.norm: 1-D scales only) — the
            # candidate encodings differ from the oracle by at most the
            # 1-D tensors' storage rounding, below the probe's
            # resolution: score 0.0 without simulating or forwarding.
            table[name] = {d: 0.0 for d in candidates}
            continue
        per: dict[str, float] = {}
        for dtype in candidates:
            sim = simulate_layer(original, dtype)
            probs = _next_token_probs(
                ctx.swapped_dev({name: sim}), ctx.model_cfg, ctx.rows
            )
            per[dtype] = kl_divergence(ctx.oracle, probs)
        table[name] = per
    return table


def probe_sensitivity(
    model_path: str,
    prompts: Sequence,
    tokenizer,
    candidates: Sequence[str] = ("int8", "int4"),
) -> dict[str, dict[str, float]]:
    """Per-layer quality impact table: swap one layer at a time to each
    candidate dtype (quantize->dequantize simulation) and measure the KL
    divergence of the next-token distribution against the bf16 oracle on
    the calibration batch. Returns ``{layer_name: {dtype: kl}}`` with an
    implicit bf16 entry of 0.0 everywhere."""
    return _probe_table(
        _probe_context(model_path, prompts, tokenizer), candidates
    )


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------


def plan_from_sensitivity(
    layer_names: Sequence[str],
    layer_bytes: Mapping[str, Mapping[str, int]],
    sensitivity: Mapping[str, Mapping[str, float]],
    *,
    bytes_budget: int | None = None,
    divergence_cap: float | None = None,
) -> PrecisionPlan:
    """Greedy dtype assignment under ONE constraint.

    Budget mode (``bytes_budget``): start uniform bf16, repeatedly take
    the downgrade step (bf16->int8 or int8->int4 on one layer) with the
    least added divergence per byte saved until estimated bytes/sweep
    fit the budget (or every layer sits at int4 — best effort, the
    estimate is reported either way). Divergence-cap mode
    (``divergence_cap``): start uniform int4, repeatedly take the
    upgrade step with the most divergence relieved per byte added until
    the estimated total fits under the cap (bf16 everywhere is 0, so the
    cap is always reachable). Deterministic: ties break by layer index.
    """
    if (bytes_budget is None) == (divergence_cap is None):
        raise ValueError(
            "give exactly one of bytes_budget / divergence_cap"
        )

    def kl(name: str, dtype: str) -> float:
        if dtype == "bf16":
            return 0.0
        return float(sensitivity[name][dtype])

    def cost(name: str, dtype: str) -> int:
        return int(layer_bytes[name][dtype])

    names = list(layer_names)
    baseline = sum(cost(n, "bf16") for n in names)
    # Candidate moves offer EVERY lower (budget mode) / higher (cap mode)
    # dtype, not just the adjacent rung: a layer whose int4 encoding
    # falls back to int8 entirely (in-dims off the quant group) has a
    # zero-relief int4->int8 step, and adjacent-only stepping would
    # strand it below bf16 forever — a cap-mode plan that can never
    # honor its own cap.
    lower = {"bf16": ("int8", "int4"), "int8": ("int4",), "int4": ()}
    higher = {"int4": ("int8", "bf16"), "int8": ("bf16",), "bf16": ()}
    if bytes_budget is not None:
        chosen = {n: "bf16" for n in names}
        total = baseline

        def downgrades():
            for i, n in enumerate(names):
                cur = chosen[n]
                for nxt in lower[cur]:
                    saved = cost(n, cur) - cost(n, nxt)
                    if saved <= 0:
                        continue
                    added = kl(n, nxt) - kl(n, cur)
                    yield (added / saved, -saved, i, n, nxt, saved)

        while total > bytes_budget:
            steps = sorted(downgrades())
            if not steps:
                break
            _, _, _, n, nxt, saved = steps[0]
            chosen[n] = nxt
            total -= saved
    else:
        chosen = {n: "int4" for n in names}

        def upgrades():
            for i, n in enumerate(names):
                cur = chosen[n]
                for nxt in higher[cur]:
                    relief = kl(n, cur) - kl(n, nxt)
                    added_bytes = max(cost(n, nxt) - cost(n, cur), 1)
                    if relief <= 0:
                        continue
                    yield (-(relief / added_bytes), i, n, nxt)

        while sum(kl(n, chosen[n]) for n in names) > divergence_cap:
            steps = sorted(upgrades())
            if not steps:
                break
            _, _, n, nxt = steps[0]
            chosen[n] = nxt

    # Dominance pass: bf16 is lossless by definition, so whenever it is
    # also no MORE bytes than the chosen dtype (a layer with nothing to
    # quantize — model.norm's 1-D scales stay fp32 under the quantizers
    # but cast to bf16), take it: strictly better on both axes, and the
    # greedy loops above never revisit a layer they already stepped.
    for n in names:
        if cost(n, "bf16") <= cost(n, chosen[n]):
            chosen[n] = "bf16"
    total = sum(cost(n, chosen[n]) for n in names)
    est_div = sum(kl(n, chosen[n]) for n in names)
    return PrecisionPlan(
        layers=tuple((n, chosen[n]) for n in names),
        # Budget mode declares the cap it ACHIEVED (the per-layer probe
        # sum, with headroom for cross-layer interaction the one-at-a-
        # time probe cannot see — build_plan tightens this to the
        # measured end-to-end value when it validates).
        divergence_cap=(
            divergence_cap
            if divergence_cap is not None
            else est_div * 1.5 + 1e-6
        ),
        bytes_budget=bytes_budget,
        est_bytes=int(total),
        baseline_bytes=int(baseline),
        est_divergence=float(est_div),
    )


def build_plan(
    model_path: str,
    prompts: Sequence,
    tokenizer,
    *,
    bytes_budget: int | None = None,
    divergence_cap: float | None = None,
    validate: bool = True,
) -> PrecisionPlan:
    """Probe + plan + validate in one call — the converter CLI's engine.

    ``validate`` re-runs the calibration batch with EVERY layer at its
    chosen dtype at once (the probe swaps one at a time) and records the
    measured end-to-end divergence; in budget mode the declared cap
    tightens to that measurement (x1.5 headroom for eval-set drift). A
    measured divergence over an explicit user cap raises — a plan that
    cannot honor its own declaration must fail at build time, not at
    serve time.

    The calibration session (model load, device conversion, oracle
    forward) is shared by the probe, the byte estimates, and the
    validation — one :class:`_ProbeContext`, not one per stage."""
    ctx = _probe_context(model_path, prompts, tokenizer)
    sens = _probe_table(ctx, ("int8", "int4"))
    sizes = {
        n: layer_dtype_bytes(ctx.host_tree(n)) for n in ctx.layer_names
    }
    plan = plan_from_sensitivity(
        ctx.layer_names,
        sizes,
        sens,
        bytes_budget=bytes_budget,
        divergence_cap=divergence_cap,
    )
    measured = None
    if validate:
        sims = {
            name: simulate_layer(ctx.host_tree(name), dt)
            for name, dt in plan.layers
        }
        measured = kl_divergence(
            ctx.oracle,
            _next_token_probs(ctx.swapped_dev(sims), ctx.model_cfg, ctx.rows),
        )
        if divergence_cap is not None and measured > divergence_cap:
            raise ValueError(
                f"planned checkpoint measures {measured:.6f} end-to-end "
                f"divergence on the calibration batch, over the requested "
                f"cap {divergence_cap:.6f} — loosen the cap or grow the "
                "calibration batch"
            )
        cap = (
            divergence_cap
            if divergence_cap is not None
            else max(measured * 1.5, plan.est_divergence * 1.5) + 1e-6
        )
        plan = dataclasses.replace(
            plan,
            measured_divergence=measured,
            divergence_cap=cap,
            calibration_prompts=len(prompts),
        )
    else:
        plan = dataclasses.replace(
            plan, calibration_prompts=len(prompts)
        )
    return plan


__all__ = [
    "PLAN_DTYPES",
    "PLAN_KIND_ACCEPTS",
    "PLAN_NAME",
    "PrecisionPlan",
    "build_plan",
    "kl_divergence",
    "layer_dtype_bytes",
    "plan_from_sensitivity",
    "plan_manifest_problems",
    "probe_sensitivity",
    "simulate_layer",
]
