"""``calibrate.py`` for a scoring cell of either driver: the readings a
cell's limits are set from, many seeds in one process. A
``score_closed_family`` cell takes its weights and reference from
``benchmark/families/<model_type>/``, a ``score_closed`` cell from the
benchmark's top level. Unlike ``calibrate.py`` (written before the residency
tier was on by default) every seed starts with the tier's pins dropped: a
pin outlives ``score_closed.release``, so ``calibrate.py``'s second seed
reads the first seed's weights. Per seed: a short window through the timed
path, the plain float32 reference over the sampled answers, the harness's
own verdict by the committed limits, and in the program's or the
reference's place:

``control_<q>``         the family's reference with its weights at ``--controls``
                        (``int8``, ``fp8``), on every ``--control-every``-th seed;
``left_out_<part>``     the reference with a part of the mathematics dropped
                        (``--leave-out sink,window,value_scale``), against the
                        sound program, on the first ``--leave-out-seeds`` seeds;
``program_int8``        the PROGRAM with its own int8 path on, on the first
                        ``--program-int8`` seeds (after the bfloat16 run's
                        memory is released; with pins off if it cannot run
                        with them: the reading is of the numerics);
``--fault alter_answer`` the planted fault, in the program's place.

    python benchmark/tools/calibrate_family.py --workload W --seeds 1,2,3 [--seconds 3]

One JSON line per seed on stdout and in ``chiprun_out/calibrate.<W>.jsonl``;
the rows' own differences in ``chiprun_out/rows.<W>.<seed>.npz``.
"""

import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check as chk, run as bench_run  # noqa: E402
from benchmark.drivers import score_closed_family as driver  # noqa: E402  (score_closed's sample; its release() drops the pins too)
from benchmark.tools.calibrate import judged  # noqa: E402


def modules_of(ctx):
    """(run, weights, reference) of the cell's driver."""
    name = ctx["traffic"]["driver"]
    if name == "score_closed_family":
        return (driver.run, driver.family_module(ctx["model"], "weights"),
                driver.family_module(ctx["model"], "reference"))
    if name == "score_closed":
        from benchmark import reference, weights
        from benchmark.drivers import score_closed
        return score_closed.run, weights, reference
    raise SystemExit(f"calibrate_family.py is for scoring cells, not driver {name!r}")


def quantized_dir(weights, model: dict, src: str, dst: str) -> float:
    """``calibrate.quantized_dir`` with the family's ``weights`` module:
    ``src`` re-encoded as int8 by the program's own ``requantize_native``,
    the distinct files through it, the repeats linked again."""
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


def program_int8(ctx, run, weights):
    """The sampled prompts again, through the program with its int8 path on.
    Returns (probability rows, seconds to quantize, "auto" or "off": the
    residency tier's budget the run went through with)."""
    import dataclasses

    from flexible_llm_sharding_tpu.runtime import orchestration

    # The bfloat16 run's pins and programs fill the chip; its answers are kept.
    driver.release(ctx, run)
    src = os.path.join(ctx["work"], "model")
    dst = os.path.join(ctx["work"], "model_int8")
    t_q = quantized_dir(weights, ctx["model"], src, dst)
    prompts = [run["kept"][k][2] for k in driver.sample_indices(ctx, run)]
    b = int(ctx["traffic"]["prompts"])

    def scored(cfg):
        out = []
        for i in range(0, len(prompts), b):
            out += orchestration.run_prompts(cfg, prompts[i:i + b], tokenizer=run["tokenizer"])
        return [np.asarray(s)[:, 0, :] for s in out]

    cfg = driver.program_config(dst, ctx["rehearsal"])
    try:
        try:
            return scored(cfg), t_q, "auto"
        except Exception as e:  # the auto budget counts stored bytes, not dequantized copies
            failed = repr(e)[:200]
        # Outside the handler: the exception's traceback held the failed run's arrays.
        ctx["log"](f"program int8 with pins at auto failed ({failed}); again with pins off")
        driver.release(ctx, run)
        return scored(dataclasses.replace(cfg, hbm_pin_gb=0.0)), t_q, "off"
    finally:
        shutil.rmtree(dst, ignore_errors=True)


def main():
    p = bench_run.parser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-every", type=int, default=1)
    p.add_argument("--controls", default="int8")
    p.add_argument("--leave-out", default="")
    p.add_argument("--leave-out-seeds", type=int, default=0)
    p.add_argument("--program-int8", type=int, default=0)
    p.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out"))
    a = p.parse_args()
    os.makedirs(a.out_dir, exist_ok=True)
    out_path = os.path.join(a.out_dir, f"calibrate.{a.workload}.jsonl")
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        a.seed = seed
        ctx = bench_run.build_ctx(a)
        if isinstance(ctx, int):
            return ctx
        run_cell, weights, reference = modules_of(ctx)
        ctx["t_process_start"] = time.monotonic()
        ctx["keep_all"] = True  # short windows: sample among all their answers
        t0 = time.monotonic()
        run = run_cell(ctx)
        rec = {"workload": a.workload, "seed": seed, "fault": a.fault,
               "t_run_s": time.monotonic() - t0, "end_to_end": run["end_to_end"],
               "info": run.get("info")}
        seqs, probs = driver.sample(ctx, run)
        rows = sum(len(s["rows"]) for s in seqs)
        probs_q = None
        if n < a.program_int8:
            try:
                probs_q, rec["t_quantize_s"], rec["program_int8_pins"] = program_int8(ctx, run, weights)
            except Exception as e:  # a control that crashes has failed, and sets no reading
                rec["program_int8_error"] = repr(e)[:400]
        driver.release(ctx, run)
        t0 = time.monotonic()
        ref = reference.forward_rows(ctx["model"], seed, seqs)
        rec["t_ref_s"] = time.monotonic() - t0
        diffs = {"program": chk.diffs(probs, ref)}
        if probs_q is not None:
            diffs["program_int8"] = chk.diffs(probs_q, ref)
        for q in a.controls.split(",") if a.control_every and n % a.control_every == 0 else []:
            lo = reference.forward_rows(ctx["model"], seed, seqs, quant=q)
            diffs[f"control_{q}"] = chk.diffs([chk.softmax(x) for x in lo], ref)
        for part in a.leave_out.split(",") if a.leave_out and n < a.leave_out_seeds else []:
            cut = reference.forward_rows(ctx["model"], seed, seqs, leave_out=(part,))
            diffs[f"left_out_{part}"] = chk.diffs(probs, cut)
        for k, d in diffs.items():
            rec[k] = judged(ctx, d, rows)
        np.savez_compressed(
            os.path.join(a.out_dir, f"rows.{a.workload}.{seed}{'.' + a.fault if a.fault else ''}.npz"),
            **{f"{k}.d": d[0].astype(np.float32) for k, d in diffs.items()},
            **{f"{k}.gap": d[1].astype(np.float32) for k, d in diffs.items()})
        line = json.dumps(rec)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
        del run, seqs, probs, ref, diffs
    shutil.rmtree(os.path.join(ROOT, ".bench_work"), ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
