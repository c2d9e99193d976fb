"""Readings the limits are set from, many seeds in one process (set-up is
long, so the contract allows it). For each seed the cell's own driver runs a
short window at the cell's own size through the timed path, then the plain
float32 reference reads the sampled answers, and the harness's own verdict
(``check.verdict`` with the committed ``limits/<cell>.json``) is printed for
the program and for everything put in its place:

``control_<q>``        the reference with its weights at ``--controls`` (``int8``,
                       ``fp8``; one scale per output channel), on every
                       ``--control-every``-th seed;
``program_int8``       the control proper: the PROGRAM with its own int8 path
                       switched on (its ``requantize_native`` encoder over the
                       run's files, the same prompts through ``run_prompts``),
                       on the first ``--program-int8`` seeds;
``--fault alter_answer`` the planted fault, in the program's place;
``--routing-look N``   on the first N seeds, the experts the reference
                       chooses at the compared rows in float32 and with
                       bfloat16 activations: rows whose choices part, beside
                       their readings.

    python benchmark/tools/calibrate.py --workload W --seeds 1,2,3 [--seconds 3]

One JSON line per seed on stdout and in ``chiprun_out/calibrate.<W>.jsonl``;
the rows' own differences (for trying another statistic without another chip
run) in ``chiprun_out/rows.<W>.<seed>.npz``.
"""

import importlib
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check as chk, reference, run as bench_run, weights  # noqa: E402


def quantized_dir(model: dict, src: str, dst: str) -> float:
    """``src`` re-encoded as int8 by the program's own ``requantize_native``:
    the distinct files go through it, the repeats are linked again and get
    their manifest entries. Returns the seconds it took."""
    from flexible_llm_sharding_tpu.integrity import manifest as integrity
    from flexible_llm_sharding_tpu.utils import checkpoint as ckpt

    t0 = time.monotonic()
    names = weights.layer_names(model)
    first: dict[str, str] = {}
    for n in names:
        first.setdefault(weights.slot_of(model, n), n)
    part = dst + ".distinct"
    os.makedirs(part)
    for n in first.values():
        os.link(os.path.join(src, n + weights.SUFFIX), os.path.join(part, n + weights.SUFFIX))
    shutil.copy(os.path.join(src, "config.json"), part)
    ckpt.requantize_native(part, dst, "int8")
    shutil.rmtree(part)
    entries = dict(integrity.load_manifest(dst)["layers"])
    for n in names:
        s = first[weights.slot_of(model, n)]
        if n != s:
            fn = n + weights.SUFFIX
            os.link(os.path.join(dst, s + weights.SUFFIX), os.path.join(dst, fn))
            entries[n] = {**entries[s], "file": fn}
    with open(os.path.join(dst, "fls_tpu_layout.json"), "w") as f:
        json.dump({"layout": "native", "dtype": "int8", "layers": names}, f)
    integrity.write_manifest(dst, {n: entries[n] for n in names})
    return time.monotonic() - t0


def judged(ctx, diffs, rows_min):
    """The numbers of one comparison with the harness's own verdict, by the
    cell's committed limits."""
    numbers = chk.summarize(*diffs)
    ok, _ = chk.verdict(numbers, ctx["traffic"]["limits"], rows_min=rows_min)
    return {**numbers, "correct": ok}


def main():
    p = bench_run.parser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-every", type=int, default=1)
    p.add_argument("--controls", default="int8")
    p.add_argument("--program-int8", type=int, default=0)
    p.add_argument("--routing-look", type=int, default=0)
    p.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out"))
    a = p.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    out_dir = a.out_dir
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"calibrate.{a.workload}.jsonl")
    for n, seed in enumerate(seeds):
        a.seed = seed
        ctx = bench_run.build_ctx(a)
        if isinstance(ctx, int):
            return ctx
        ctx["t_process_start"] = time.monotonic()
        ctx["keep_all"] = True  # short windows: sample among all their answers
        driver = importlib.import_module(f"benchmark.drivers.{ctx['traffic']['driver']}")
        t0 = time.monotonic()
        run = driver.run(ctx)
        t_run = time.monotonic() - t0
        seqs, probs = driver.sample(ctx, run)
        rows = sum(len(s["rows"]) for s in seqs)
        rec = {"workload": a.workload, "seed": seed, "fault": a.fault, "t_run_s": t_run,
               "end_to_end": run["end_to_end"], "info": run.get("info")}
        probs_q = None
        if n < a.program_int8:
            try:
                probs_q, rec["t_quantize_s"] = program_int8(ctx, run, driver)
            except Exception as e:  # a control that crashes has failed, and sets no reading
                rec["program_int8_error"] = repr(e)[:400]
        driver.release(ctx, run)
        t0 = time.monotonic()
        taps32 = [] if n < a.routing_look else None
        ref = reference.forward_rows(ctx["model"], seed, seqs, taps=taps32)
        rec["t_ref_s"] = time.monotonic() - t0
        diffs = {"program": chk.diffs(probs, ref)}
        if probs_q is not None:
            diffs["program_int8"] = chk.diffs(probs_q, ref)
        for q in a.controls.split(",") if a.control_every and n % a.control_every == 0 else []:
            lo = reference.forward_rows(ctx["model"], seed, seqs, quant=q)
            diffs[f"control_{q}"] = chk.diffs([chk.softmax(x) for x in lo], ref)
        if taps32 is not None:
            taps16 = []
            lo = reference.forward_rows(ctx["model"], seed, seqs, quant="bf16_act", taps=taps16)
            diffs["reference_bf16_act"] = chk.diffs([chk.softmax(x) for x in lo], ref)
            parted = np.concatenate([  # per row: layers in which the chosen experts differ
                sum((np.asarray(l32[i]) != np.asarray(l16[i])).any(-1).astype(int)
                    for l32, l16 in zip(taps32, taps16)) for i in range(len(seqs))])
            rms = np.sqrt((diffs["reference_bf16_act"][0] ** 2).mean(-1))
            rec["routing_look"] = {
                "layers_parted_per_row": parted.tolist(),
                "row_rms_reference_bf16_act": [round(float(x), 4) for x in rms]}
        for k, d in diffs.items():
            rec[k] = judged(ctx, d, rows)
        np.savez_compressed(
            os.path.join(out_dir, f"rows.{a.workload}.{seed}{'.' + a.fault if a.fault else ''}.npz"),
            **{f"{k}.d": d[0].astype(np.float32) for k, d in diffs.items()},
            **{f"{k}.gap": d[1].astype(np.float32) for k, d in diffs.items()})
        line = json.dumps(rec)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
        del run, seqs, probs, ref, diffs
    shutil.rmtree(os.path.join(ROOT, ".bench_work"), ignore_errors=True)
    return 0


def program_int8(ctx, run, driver):
    """The sampled prompts again, through the program with its int8 path on:
    the run's own files re-encoded by the program's encoder, the same call
    (``run_prompts``), batches of the cell's own size."""
    from flexible_llm_sharding_tpu.runtime import orchestration

    src = os.path.join(ctx["work"], "model")
    dst = os.path.join(ctx["work"], "model_int8")
    t_q = quantized_dir(ctx["model"], src, dst)
    cfg = driver.program_config(dst, ctx["rehearsal"])
    picked = driver.sample_indices(ctx, run)
    prompts = [run["kept"][k][2] for k in picked]
    b = int(ctx["traffic"]["prompts"])
    scores = []
    for i in range(0, len(prompts), b):
        scores += orchestration.run_prompts(cfg, prompts[i:i + b], tokenizer=run["tokenizer"])
    shutil.rmtree(dst, ignore_errors=True)
    return [np.asarray(s)[:, 0, :] for s in scores], t_q


if __name__ == "__main__":
    sys.exit(main())
