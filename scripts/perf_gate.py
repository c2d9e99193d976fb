#!/usr/bin/env python
"""CI perf gate (ROADMAP item 5, first half): run the CPU-cheap bench
phases on every PR and fail on regression beyond the recorded spread.

bench.py has rich phases but ran ad hoc — a host-path regression (a copy
sneaking onto the zero-copy stream, the shard cache silently missing, the
pin tier streaming pinned bytes anyway) could land unnoticed until the
next chip run. This gate runs the phases that are meaningful on a
CPU-only runner:

- ``host_stream_*_warm_gbps``  (bench_host_stream, warm legs only — cold
  eviction is disk-noise on shared CI runners)
- ``warm_sweep_speedup`` / ``host_cache_hit_rate``  (bench_host_cache)
- ``partial_residency_speedup``  (bench_residency)
- ``mixedprec_bytes_saved_frac``  (bench_mixedprec — structural byte
  counters; the phase itself asserts divergence under the plan's cap)
- ``vs_reference_schedule``  (bench_reference_schedule — the schedule win
  exists without a transfer link: batching, stacked scans, async uploads)

and compares each against the floor recorded in ``PERF_GATE.json``.
Floors are deliberately set WELL below the recorded values (see the
``floor_rule`` field per metric): CI runners are slower and noisier than
the recording rig, and the gate exists to catch order-of-magnitude
regressions and lost mechanisms, not percent-level drift — with two
exceptions. Mechanism ratios whose regression signature is "collapses to
parity" are clamped to a floor of at least 1.0 (``PARITY_CLAMPED`` — a
floor below 1.0 passes the exact failure the metric exists to catch),
and ``pinned_fraction`` is a structural, timing-free detector for the
pin tier disengaging entirely.

Usage:
    python scripts/perf_gate.py            # gate: exit 1 on regression
    python scripts/perf_gate.py --record   # re-record PERF_GATE.json
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

GATE_PATH = os.path.join(ROOT, "PERF_GATE.json")

# metric -> fraction of the recorded value used as the failure floor.
# Ratio metrics get a tight-ish fraction (mechanism lost => ratio ~1 or
# below); absolute throughput gets a loose one (runner hardware varies).
FLOOR_RULES = {
    "host_stream_zero_copy_warm_gbps": 0.15,
    "host_stream_cast_warm_gbps": 0.15,
    # Cache lost => ratio collapses to ~1; disk/CPU balance shifts the
    # healthy value a lot between runners, so the floor sits low.
    "warm_sweep_speedup": 0.25,
    "host_cache_hit_rate": 0.95,  # structural: 2/3 at an unbounded budget
    # Pin tier regressed => the pinned arm stops beating streaming (the
    # CPU rig's healthy ratio is small by design — device_put is a
    # memcpy — so the rule alone would land BELOW parity; the parity
    # clamp keeps "no better than streaming" a failure).
    "partial_residency_speedup": 0.90,
    # Structural, timing-free: the planner pinned ~half the model's bytes.
    # This is the tier-disengaged detector (tier_for returning None makes
    # the speedup arm measure ~1.0, which parity alone could miss inside
    # noise; the fraction collapsing to 0 cannot hide).
    "pinned_fraction": 0.95,
    # Mixed-precision streaming (ISSUE 14): fraction of the uniform-bf16
    # sweep bytes a 0.6x-budget plan removes from the link, read from the
    # executors' own streamed_bytes counters — structural and timing-free
    # (the phase asserts divergence under the plan's declared cap BEFORE
    # recording, so a number here is a quality-proven number). The
    # acceptance criterion is >= 0.35 saved; the recorded value sits near
    # 0.40 by construction of the 0.6x budget, so the 0.95 rule keeps the
    # floor above the criterion — a plan/converter/accounting regression
    # collapses the fraction toward 0, which no runner noise can fake.
    "mixedprec_bytes_saved_frac": 0.95,
    # "our schedule no better than the reference emulation" is the
    # regression this exists to catch.
    "vs_reference_schedule": 0.80,
    # Span tracing crept onto the hot path (trace-off wall / trace-on
    # wall sinking well below parity). Advisory: the healthy value IS
    # parity, so a hard floor near 1.0 would flake on runner noise.
    "trace_overhead_ratio": 0.85,
    # Flight recorder armed vs off on an identical serve session (the
    # journal's emit sites are failure paths only, so durability must
    # cost noise). Advisory for the same reason as trace_overhead_ratio:
    # the healthy value IS parity.
    "recorder_overhead_ratio": 0.85,
    # Speculative decoding, both halves of the claim (ISSUE 13 — the TPU
    # capture once disowned its spec numbers as clock drift; these rules
    # exist so the claim can never rot silently again):
    # - the offline MECHANISM wall ratio (replay drafts, acceptance 1.0,
    #   rotation-paired). Advisory: a timing ratio on shared runners.
    "spec_mechanism_speedup": 0.60,
    # - the SERVING tokens-per-sweep headline under the same replay
    #   source. Structural and timing-free (sweep counts, not walls):
    #   the verify pass disengaging collapses it to ~1 token/sweep,
    #   which no runner noise can fake — so this one gates hard, the
    #   pinned_fraction precedent.
    "spec_serve_tokens_per_sweep": 0.95,
    # Resident draft model + adaptive k (ISSUE 20): tokens-per-sweep
    # with the REAL draft path live end to end — runtime/draft.py pinned
    # through its residency tier (the phase refuses to record unless
    # adaptive per-sweep streamed bytes equal plain's exactly) and the
    # serve/spec.py controller climbing k on windowed acceptance.
    # Structural and timing-free (sweep counts + byte counters): the
    # draft model failing to draft, the controller failing to raise k,
    # or the verifier disengaging each collapse it toward ~1
    # token/sweep, which no runner noise can fake — hard gate, the
    # pinned_fraction precedent.
    "spec_adaptive_tokens_per_sweep": 0.95,
    # The controller's acceptance-driven trajectory: largest per-class k
    # reached under deterministic acceptance 1.0. Integer-exact on a
    # fixed workload; staying at the starting k means the observe/raise
    # loop is dead.
    "spec_adaptive_k_final": 0.95,
    # Paged prefix-KV pool (ISSUE 16): fraction of total prefix prefill
    # work the second same-prefix wave serves from pooled pages, read
    # from the engine's own token counters — structural and timing-free
    # (two same-prefix waves put the healthy value at exactly 0.5; the
    # phase asserts pool-on/pool-off token-identity BEFORE recording).
    # The pool disengaging collapses it to 0.0, which no runner noise
    # can fake — so this gates hard, the pinned_fraction precedent.
    "kv_prefix_reuse_frac": 0.95,
    # Multi-tenant LoRA serving (ISSUE 17): base-only wall / adapters-on
    # wall on the identical two-tenants-plus-base workload (warm passes;
    # base-row token-identity and nonzero applied delta rows asserted by
    # the phase before recording). Advisory: the healthy value IS parity
    # — the deltas ride the existing sweep — so a hard floor near 1.0
    # would flake on runner noise, while the structural claim (delta
    # bytes a rank-sized sliver of the streamed base bytes) is asserted
    # as a hard <0.05 ceiling inside the bench phase itself, because the
    # healthy fraction (~1e-4) rounds any recorded-value floor to zero.
    "adapter_overhead_ratio": 0.85,
    # Crash-safe serving (ISSUE 18): WAL-off wall / WAL-on wall on the
    # identical small serve session under the default fsync policy
    # (admit/terminal fsync only; sweep-boundary progress rides the
    # kernel buffers). Advisory: the healthy value IS parity — WAL
    # writes are per request event and per sweep boundary, never per
    # token/shard — so a hard floor near 1.0 would flake on runner
    # noise; what the tripwire watches is journaling or fsync creeping
    # onto the per-shard hot path.
    "wal_overhead_ratio": 0.85,
    # Closed-loop sweep stagger (ISSUE 19): 1 - final stagger error of a
    # deterministic synthetic-clock loop driving the REAL controller —
    # two in-phase replicas must converge to the i/N offsets and
    # re-converge after a simulated recycle, with the phase refusing to
    # record unless boundary holds actually fired in both rounds.
    # Structural and timing-free (injected clocks everywhere): healthy
    # is 1.0 by construction; the hold math disengaging leaves the
    # initial error standing and collapses this toward 0, which no
    # runner noise can fake — so it gates hard, the pinned_fraction
    # precedent.
    "fleet_stagger_convergence": 0.95,
}

# Ratios whose loss-of-mechanism signature is "collapses to parity": the
# floor never sits below 1.0, whatever the recorded value times the rule
# works out to — a gate that passes at 1.0 cannot catch the one
# regression it documents. Only ADVISORY metrics belong here: a hard
# floor clamped above the rig's own recorded dispersion would fail runs
# the recording itself produced.
PARITY_CLAMPED = {"partial_residency_speedup"}

# Advisory-only metrics: a miss is logged loudly in the job output but
# does not fail CI. partial_residency_speedup's healthy CPU value sits
# close to parity by design (device_put is a memcpy), so a hard parity
# floor would flake on shared runners — while the regression it exists
# for (tier disengaged) is already caught deterministically by the
# structural pinned_fraction floor. trace_overhead_ratio's healthy value
# is parity by CONSTRUCTION (tracing must be free), so its floor is an
# advisory tripwire for span recording creeping onto the hot path, not
# a hard line runner noise could cross. spec_mechanism_speedup is a
# wall-clock ratio whose healthy CPU value varies with the runner's
# disk/CPU balance; the regression it watches (verification no longer
# amortizing weight streams) is caught deterministically by the hard
# structural spec_serve_tokens_per_sweep floor, so the wall ratio stays
# advisory.
ADVISORY = {
    "partial_residency_speedup",
    "trace_overhead_ratio",
    "recorder_overhead_ratio",
    "spec_mechanism_speedup",
    "adapter_overhead_ratio",
    "wal_overhead_ratio",
}

# Hard metrics with a sub-parity WARN band: the hard floor derives from
# the WORST recorded pair (the spread) — the recording rig itself has
# produced sub-parity readings when healthy (vs_reference_schedule
# spread min 0.991), so parity cannot be a hard line without flaking.
# A reading below 1.0 but above the floor passes with a loud warning;
# below the floor (worse than anything the healthy rig ever measured)
# fails.
PARITY_WARN = {"vs_reference_schedule"}


def _floor(
    key: str, recorded: float, frac: float, spread=None
) -> float:
    # Gate against the worst value the recording rig itself produced —
    # a floor above min(spread) flakes on dispersion the metric is known
    # to have, regardless of how healthy the median looks.
    base = min(spread) if spread else recorded
    floor = base * frac
    if key in PARITY_CLAMPED:
        floor = max(floor, 1.0)
    return round(floor, 3)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure() -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import bench
    from bench import (
        BenchTokenizer,
        bench_adapters,
        bench_fleet_stagger,
        bench_host_cache,
        bench_host_stream,
        bench_kv_reuse,
        bench_mixedprec,
        bench_recorder_overhead,
        bench_reference_schedule,
        bench_residency,
        bench_spec,
        bench_spec_adaptive,
        bench_spec_serve,
        bench_trace_overhead,
        bench_wal_overhead,
        make_model,
        make_prompts,
    )
    from flexible_llm_sharding_tpu.config import FrameworkConfig

    cfg_kwargs = dict(
        vocab_size=32000,
        hidden_size=1024,
        intermediate_size=2816,
        num_hidden_layers=4,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=4096,
    )
    model_path = make_model(jax, cfg_kwargs)
    prompts = make_prompts(n=2, prefix_words=180, suffix_words=24, n_suffix=4)
    tok = BenchTokenizer()

    def fw(prefetch):
        return FrameworkConfig(
            model_path=model_path,
            layer_num_per_shard=1,
            storage_location="cpu",
            dtype="bfloat16",
            block_size=8,
            prefetch_depth=prefetch,
            disk_folder=os.path.join(bench.BENCH_DIR, "acts"),
        )

    result: dict = {}
    # A constant 0.8 budget keeps every warm leg while skipping
    # bench_host_stream's cold-eviction legs (>0.85 gate there) — cold
    # disk behaviour on a shared CI runner is noise, not signal.
    budget = lambda: 0.8  # noqa: E731
    t0 = time.perf_counter()
    bench_host_stream(result, model_path, budget)
    bench_host_cache(result, model_path, budget, jax.devices()[0])
    bench_residency(result, model_path, prompts, tok, budget, fw)
    bench_mixedprec(result, model_path, prompts, tok, budget, fw)
    bench_trace_overhead(result, prompts, tok, budget, fw)
    bench_recorder_overhead(result, prompts, tok, budget, fw)
    bench_wal_overhead(result, prompts, tok, budget, fw)
    bench_reference_schedule(jax, fw(None), prompts, tok, result, budget)
    # Speculative decoding (ISSUE 13): small token/draft budgets — the
    # gate needs the mechanism witnessed, not the full-depth measurement
    # the TPU capture runs (bench.py defaults).
    bench_spec(fw(None), tok, result, budget, n_tok=4, k=4)
    bench_spec_serve(fw(None), tok, result, budget)
    # Resident draft model + adaptive k (ISSUE 20): small token budget —
    # the gate needs the control loop and the zero-extra-stream claim
    # witnessed (both asserted inside the phase), not full depth.
    bench_spec_adaptive(fw(None), tok, result, budget, n_tok=8, k_max=5)
    # Paged prefix-KV pool (ISSUE 16): small token budget — the gate
    # needs cross-wave reuse witnessed, not a throughput measurement.
    bench_kv_reuse(fw(None), tok, result, budget, n_tok=4)
    # Multi-tenant LoRA (ISSUE 17): small token budget — the gate needs
    # parity + rank-sized delta bytes witnessed, not a full measurement.
    bench_adapters(fw(None), tok, result, budget, n_tok=4)
    # Closed-loop sweep stagger (ISSUE 19): deterministic synthetic-clock
    # loop over the real controller — milliseconds, no model in the loop.
    bench_fleet_stagger(result)
    result["gate_wall_s"] = round(time.perf_counter() - t0, 1)
    return result


def main() -> int:
    record = "--record" in sys.argv
    result = measure()
    log(f"measured: {json.dumps({k: result.get(k) for k in FLOOR_RULES})}")

    if record:
        gate = {
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "metrics": {},
        }
        for key, frac in FLOOR_RULES.items():
            val = result.get(key)
            if val is None:
                log(f"record: {key} missing from the measurement — aborting")
                return 1
            spread = result.get(f"{key}_spread")
            entry = {
                "recorded": val,
                "floor": _floor(key, val, frac, spread),
                "floor_rule": frac,
            }
            if spread is not None:
                entry["spread"] = spread
            gate["metrics"][key] = entry
        with open(GATE_PATH, "w") as f:
            json.dump(gate, f, indent=1)
        log(f"recorded -> {GATE_PATH}")
        return 0

    try:
        with open(GATE_PATH) as f:
            gate = json.load(f)
    except (OSError, ValueError) as e:
        log(f"no usable {GATE_PATH} ({e!r}); run with --record first")
        return 1
    failures = []
    warnings = []
    report = {}
    for key, entry in gate["metrics"].items():
        val = result.get(key)
        # Re-derive the floor at gate time too: a stale or hand-edited
        # recording can neither weaken the parity clamp nor re-tighten a
        # spread-derived floor back to the flaky median-based one.
        if "floor_rule" in entry:
            floor = _floor(
                key, entry["recorded"], entry["floor_rule"],
                entry.get("spread"),
            )
        else:
            floor = entry["floor"]
            if key in PARITY_CLAMPED:
                floor = max(floor, 1.0)
        report[key] = {
            "measured": val,
            "floor": floor,
            "recorded": entry["recorded"],
        }
        if key in ADVISORY:
            report[key]["advisory"] = True
        miss = None
        if val is None:
            miss = f"{key}: phase produced no value (broke?)"
        elif val < floor:
            miss = (
                f"{key}: {val} < floor {floor} "
                f"(recorded {entry['recorded']})"
            )
        if miss is None:
            if key in PARITY_WARN and val < 1.0:
                warnings.append(
                    f"{key}: {val} below parity but above floor {floor} "
                    f"(the recorded spread itself dips to "
                    f"{min(entry.get('spread') or [entry['recorded']])}; "
                    "watch for a trend)"
                )
            continue
        # A phase that produced NO value is a breakage, never advisory.
        if key in ADVISORY and val is not None:
            warnings.append(miss)
        else:
            failures.append(miss)
    # A metric added to FLOOR_RULES but absent from the recorded gate
    # would otherwise be silently ungated until someone re-records —
    # the exact silent-cap failure mode this script exists to prevent.
    for key in FLOOR_RULES:
        if key not in gate["metrics"]:
            failures.append(
                f"{key}: in FLOOR_RULES but missing from the recorded "
                f"gate — re-run with --record"
            )
    print(
        json.dumps(
            {"perf_gate": report, "failures": failures, "warnings": warnings}
        )
    )
    for w in warnings:
        log(f"PERF GATE ADVISORY (not failing CI): {w}")
    if failures:
        log("PERF GATE FAILED:")
        for f_ in failures:
            log(f"  {f_}")
        return 1
    log("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
