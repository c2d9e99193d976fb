"""Link probe (one chip call, by hand): what a streamed layer's upload is
made FROM decides how fast it crosses the host->HBM link and whether a
program runs beside it.

One tree of a Moonlight expert layer's shapes (1.17 GB bf16: the three
``[64, ...]`` expert leaves and the small ones) is uploaded

  (i)  alone, and
  (ii) beside a second thread that loops a jitted program of ~40 ms on
       resident operands and blocks on every fourth,

from six sources: (a) an ``mmap`` view of a file in the page cache (what
``_build_host_shard`` hands ``_place`` today), (b) an anonymous NumPy copy,
(c) a page-aligned anonymous copy, (d) ``jax.Array`` leaves in the chip's
``pinned_host`` memory moved with a memory-space ``device_put``, (e) the
tree of (d) in pieces of at most 64 MB, (f) two uploads of (d) at once.
Beside the table: how fast a NumPy tree becomes a ``pinned_host`` one, how
many such trees the host takes, small transfers (an activation's size)
beside the uploads, and ``TPU_PREMAPPED_BUFFER_SIZE`` as the chip tool's
environment has it.

    python scripts/link_probe.py            # on the chip; ~2 min
    JAX_PLATFORMS=cpu python scripts/link_probe.py --toy   # control flow

Writes ``chiprun_out/link_probe.json`` and prints the table. The decision
rule it is read by is ISSUE 30's (PERF.md section 6, PR 30).
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

PIECE_BYTES = 64 << 20


def layer_specs(toy: bool) -> list[tuple[str, tuple[int, ...]]]:
    """The leaves of one Moonlight expert layer (benchmark/weights.py's
    own table over the cell's configuration)."""
    from benchmark import weights

    with open(os.path.join(ROOT, "benchmark/configs/moonlight-16b-a3b.json")) as f:
        model = json.load(f)
    if toy:
        model.update(model["rehearsal"])
    layer = f"model.layers.{int(model['first_k_dense_replace'])}"
    return [(k, shape) for k, shape, _ in weights.tensor_specs(model, layer)]


def make_sources(specs, workdir: str):
    """The tree as (a) mmap views of a page-cached file, (b) anonymous
    copies, (c) page-aligned anonymous copies."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    rng = np.random.default_rng(30)
    path = os.path.join(workdir, "layer.bin")
    offsets, off = {}, 0
    with open(path, "wb") as f:
        for key, shape in specs:
            n = int(np.prod(shape))
            bits = rng.integers(0, 1 << 16, size=n, dtype=np.uint16)
            offsets[key] = off
            f.write(bits.tobytes())
            off += n * 2
            off_pad = -off % 64  # safetensors-like: leaves not page-aligned
            f.write(b"\0" * off_pad)
            off += off_pad
    mm = np.memmap(path, mode="r", dtype=np.uint8)
    _ = int(np.add.reduce(mm[:: 4096], dtype=np.uint64))  # in the page cache
    a, b, c = {}, {}, {}
    for key, shape in specs:
        n = int(np.prod(shape)) * 2
        view = mm[offsets[key]: offsets[key] + n].view(bf16).reshape(shape)
        a[key] = view[None]  # the loader's k=1 [None] view
        b[key] = np.array(view)[None]
        buf = mmap.mmap(-1, max(n, 1))  # anonymous, page-aligned
        arr = np.frombuffer(buf, dtype=bf16, count=n // 2).reshape(shape)
        arr[...] = view
        c[key] = arr[None]
    return a, b, c, off


def pieces(tree: dict) -> dict:
    """Every leaf cut along its second axis (the expert axis of the large
    ones) into pieces of at most 64 MB."""
    out = {}
    for key, x in tree.items():
        if x.nbytes <= PIECE_BYTES or x.ndim < 2:
            out[key] = x
            continue
        per = max(1, x.shape[1] * PIECE_BYTES // x.nbytes)
        for j in range(0, x.shape[1], per):
            out[f"{key}.{j}"] = x[:, j: j + per]
    return out


class Loop:
    """The compute thread: a jitted program on resident operands, blocked
    on at every fourth call; ``stamps`` holds (time, programs done)."""

    def __init__(self, step, x, w, iters):
        self.step, self.x, self.w, self.iters = step, x, w, iters
        self.stamps: list[tuple[float, int]] = []
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import jax

        n, y = 0, self.x
        while not self._stop.is_set():
            for _ in range(4):
                y = self.step(y, self.w, self.iters)
                n += 1
            jax.block_until_ready(y)
            self.stamps.append((time.perf_counter(), n))

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join()

    def rate(self, t_lo: float, t_hi: float) -> float:
        """Programs a second over the stamps inside [t_lo, t_hi]."""
        inside = [(t, n) for t, n in self.stamps if t_lo <= t <= t_hi]
        if len(inside) < 2:
            return 0.0
        return (inside[-1][1] - inside[0][1]) / (inside[-1][0] - inside[0][0])


def upload_runs(put, nbytes: int, reps: int, min_s: float = 0.0) -> dict:
    """At least ``reps`` uploads and ``min_s`` seconds of them, back to
    back, each timed dispatch -> ready."""
    import jax

    walls, dispatch = [], []
    t_lo = time.perf_counter()
    while len(walls) < reps or time.perf_counter() - t_lo < min_s:
        t0 = time.perf_counter()
        out = put()
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        del out
        walls.append(t2 - t0)
        dispatch.append(t1 - t0)
    t_hi = time.perf_counter()
    walls.sort()
    return {
        "gbps": nbytes / 1e9 / walls[len(walls) // 2],
        "gbps_window": len(walls) * nbytes / 1e9 / (t_hi - t_lo),
        "uploads": len(walls),
        "upload_s_median": walls[len(walls) // 2],
        "upload_s_max": walls[-1],
        "dispatch_s_median": sorted(dispatch)[len(dispatch) // 2],
        "t_lo": t_lo,
        "t_hi": t_hi,
    }


def write(report: dict) -> None:
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "link_probe.json"), "w") as f:
        json.dump(report, f, indent=1)


def copy_probe(toy: bool) -> int:
    """What making a ``pinned_host`` tree costs, by the way it is made: from
    NumPy (one leaf of 369 MB, one of 16 MB), by allocation alone, from the
    device's own copy (a device->host transfer in the device's layout), and
    again after a free. Run once as the environment stands and once with
    ``TPU_PREMAPPED_BUFFER_SIZE`` set, each in a process of its own."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    on_device = SingleDeviceSharding(dev, memory_kind="device")
    pinned = SingleDeviceSharding(dev, memory_kind="pinned_host")
    bf16 = np.dtype(ml_dtypes.bfloat16)
    rng = np.random.default_rng(31)
    big_shape = (1, 8, 64, 32) if toy else (1, 64, 2048, 1408)
    small_shape = (1, 8, 64, 16) if toy else (1, 64, 2048, 64)
    big = rng.integers(0, 1 << 16, size=big_shape, dtype=np.uint16).view(bf16)
    small = rng.integers(0, 1 << 16, size=small_shape, dtype=np.uint16).view(bf16)
    out: dict = {"premapped": os.environ.get("TPU_PREMAPPED_BUFFER_SIZE"),
                 "big_bytes": big.nbytes, "small_bytes": small.nbytes}

    def timed(fn):
        t0 = time.perf_counter()
        r = jax.block_until_ready(fn())
        return r, time.perf_counter() - t0

    def gbps(nbytes, s):
        return nbytes / 1e9 / s

    for name, x in (("small", small), ("big", big)):
        jax.block_until_ready(jax.device_put(x, dev))
        h, s1 = timed(lambda: jax.device_put(x, pinned))
        _, s2 = timed(lambda: jax.device_put(x, pinned))  # a second, the first still held
        out[f"numpy_to_pinned_{name}_gbps"] = [gbps(x.nbytes, s1), gbps(x.nbytes, s2)]
        d, s3 = timed(lambda: jax.device_put(x, dev))
        out[f"numpy_to_device_{name}_gbps"] = gbps(x.nbytes, s3)
        p, s4 = timed(lambda: jax.device_put(d, pinned))
        p2, s5 = timed(lambda: jax.device_put(d, pinned))
        out[f"device_to_pinned_{name}_gbps"] = [gbps(x.nbytes, s4), gbps(x.nbytes, s5)]
        back, s6 = timed(lambda: jax.device_put(p, on_device))
        out[f"pinned_to_device_{name}_gbps"] = gbps(x.nbytes, s6)
        out[f"round_trip_equal_{name}"] = bool(
            np.array_equal(np.asarray(back).view(np.uint16), x.view(np.uint16))
            and p.sharding.memory_kind == "pinned_host"
        )
        zeros = jax.jit(lambda: jnp.zeros(x.shape, jnp.bfloat16), out_shardings=pinned)
        try:  # the CPU backend has no host-placed program output
            timed(zeros)
            _, s7 = timed(zeros)
            out[f"alloc_zeros_pinned_{name}_gbps"] = gbps(x.nbytes, s7)
        except Exception as exc:  # noqa: BLE001
            out[f"alloc_zeros_pinned_{name}_error"] = repr(exc)[:120]
        del h, p, p2, back
        _, s8 = timed(lambda: jax.device_put(d, pinned))  # after a free
        out[f"device_to_pinned_after_free_{name}_gbps"] = gbps(x.nbytes, s8)
        _, s9 = timed(lambda: jax.device_put(x, pinned))
        out[f"numpy_to_pinned_after_free_{name}_gbps"] = gbps(x.nbytes, s9)
    # Do pinned allocations made side by side add up? Eight big leaves by
    # one, two and four threads.
    from concurrent.futures import ThreadPoolExecutor

    for workers in (1, 2, 4):
        t0 = time.perf_counter()
        with ThreadPoolExecutor(workers) as pool:
            held = list(pool.map(
                lambda _: jax.block_until_ready(jax.device_put(big, pinned)), range(8)
            ))
        out[f"numpy_to_pinned_8_leaves_{workers}_threads_gbps"] = gbps(
            8 * big.nbytes, time.perf_counter() - t0
        )
        del held
    # The table's two ends under this environment: (a)-like NumPy and (d).
    p, _ = timed(lambda: jax.device_put(big, pinned))
    for name, put in (("numpy", lambda: jax.device_put(big, dev)),
                      ("pinned", lambda: jax.device_put(p, on_device))):
        put()
        out[f"upload_{name}_gbps"] = upload_runs(put, big.nbytes, 6)["gbps"]
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = "premapped" if out["premapped"] else "default"
    with open(os.path.join(ROOT, "chiprun_out", f"link_probe_copy.{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--copy", action="store_true", help="only: what a pinned_host copy costs")
    ap.add_argument("--toy", action="store_true", help="rehearsal widths")
    ap.add_argument("--reps", type=int, default=8, help="uploads alone")
    ap.add_argument("--beside-s", type=float, default=3.0, help="seconds of uploads beside the loop")
    ap.add_argument("--hold", type=int, default=8, help="pinned trees to try to hold at once")
    args = ap.parse_args()
    if args.copy:
        return copy_probe(args.toy)

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[0]
    report: dict = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "jax": jax.__version__,
        "memories": [m.kind for m in dev.addressable_memories()],
        "env": {
            k: os.environ.get(k)
            for k in ("TPU_PREMAPPED_BUFFER_SIZE", "TPU_PREMAPPED_BUFFER_TRANSFER_THRESHOLD_BYTES",
                      "LIBTPU_INIT_ARGS", "XLA_FLAGS", "JAX_PLATFORMS")
        },
        "cpu_count": os.cpu_count(),
    }
    print(json.dumps(report), flush=True)
    on_device = SingleDeviceSharding(dev, memory_kind="device")
    pinned = SingleDeviceSharding(dev, memory_kind="pinned_host")

    specs = layer_specs(args.toy)
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as workdir:
        a, b, c, _ = make_sources(specs, workdir)
        nbytes = sum(x.nbytes for x in a.values())
        report["tree_bytes"] = nbytes

        # NumPy -> pinned_host: the copy Step 1 would make once per file
        # generation, from the mmap view and from an anonymous copy.
        to_pinned = {}
        for name, src in (("mmap", a), ("anon", b)):
            t0 = time.perf_counter()
            d = jax.device_put(src, pinned)
            jax.block_until_ready(d)
            to_pinned[name] = nbytes / 1e9 / (time.perf_counter() - t0)
            if name == "mmap":
                del d
        report["numpy_to_pinned_host_gbps"] = to_pinned
        assert all(x.sharding.memory_kind == "pinned_host" for x in d.values())
        e = jax.device_put(pieces(b), pinned)
        jax.block_until_ready(e)
        assert all(x.sharding.memory_kind == "pinned_host" for x in e.values())
        report["pieces"] = len(e)

        def put_np(tree):
            return lambda: jax.device_put(tree, dev)

        def put_pinned(tree):
            return lambda: jax.device_put(tree, on_device)

        def put_two():
            box = []
            t = threading.Thread(target=lambda: box.append(jax.device_put(d, on_device)))
            t.start()
            mine = jax.device_put(d, on_device)
            t.join()
            return mine, box[0]

        sources = [
            ("a_mmap", put_np(a), nbytes),
            ("b_anon", put_np(b), nbytes),
            ("c_aligned", put_np(c), nbytes),
            ("d_pinned_host", put_pinned(d), nbytes),
            ("e_pinned_64MB", put_pinned(e), nbytes),
            ("f_two_pinned", put_two, 2 * nbytes),
        ]

        # The compute loop: y = tanh(y @ w), ``iters`` times; iters is a
        # traced bound, calibrated to ~40 ms a call.
        n = 256 if args.toy else 4096
        step = jax.jit(
            lambda y, w, iters: jax.lax.fori_loop(
                0, iters, lambda _, v: jnp.tanh(v @ w).astype(v.dtype), y
            )
        )
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.bfloat16)
        w = (jax.random.normal(key, (n, n), jnp.float32) * n ** -0.5).astype(jnp.bfloat16)
        jax.block_until_ready(step(x, w, 4))
        t0 = time.perf_counter()
        jax.block_until_ready(step(x, w, 32))
        per_iter = (time.perf_counter() - t0) / 32
        iters = max(1, int(round(0.040 / per_iter)))
        t0 = time.perf_counter()
        jax.block_until_ready(step(x, w, iters))
        report["program_s"] = time.perf_counter() - t0
        report["program_iters"] = iters

        with Loop(step, x, w, iters) as loop:
            time.sleep(0.5 if args.toy else 3.0)
        loop_alone = loop.rate(0, float("inf"))
        report["loop_alone_programs_per_s"] = loop_alone

        rows = []
        for name, put, size in sources:
            jax.block_until_ready(put())  # warm: first touch, page faults
            alone = upload_runs(put, size, args.reps)
            with Loop(step, x, w, iters) as loop:
                time.sleep(0.3)
                beside = upload_runs(put, size, args.reps, args.beside_s)
                time.sleep(0.3)
            loop_beside = loop.rate(beside["t_lo"], beside["t_hi"])
            row = {
                "source": name,
                "alone_gbps": alone["gbps"],
                "alone_gbps_window": alone["gbps_window"],
                "alone_dispatch_s": alone["dispatch_s_median"],
                "beside_gbps": beside["gbps"],
                "beside_gbps_window": beside["gbps_window"],
                "beside_upload_s_max": beside["upload_s_max"],
                "beside_uploads": beside["uploads"],
                "beside_dispatch_s": beside["dispatch_s_median"],
                "loop_beside_programs_per_s": loop_beside,
                "loop_kept": loop_beside / loop_alone if loop_alone else 0.0,
                "upload_kept": beside["gbps"] / alone["gbps"],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
        report["rows"] = rows
        write(report)

        # Small transfers beside the uploads: an activation block's size
        # (a few MB) host->device and back, timed alone and while (a) and
        # (d) upload on another thread. Says whether the activation store's
        # per-block copies would queue behind weight uploads.
        act = np.ones((8, 768, n // 2 if args.toy else 2048), a[specs[0][0]].dtype)
        act_dev = jax.device_put(act, dev)

        def small(reps=40):
            h2d, d2h = [], []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(jax.device_put(act, dev))
                t1 = time.perf_counter()
                np.asarray(act_dev + 0)
                t2 = time.perf_counter()
                h2d.append(t1 - t0)
                d2h.append(t2 - t1)
            h2d.sort()
            d2h.sort()
            return {"h2d_ms_median": 1e3 * h2d[len(h2d) // 2], "h2d_ms_max": 1e3 * h2d[-1],
                    "d2h_ms_median": 1e3 * d2h[len(d2h) // 2], "d2h_ms_max": 1e3 * d2h[-1]}

        small(4)
        acts = {"bytes": act.nbytes, "alone": small()}
        for name, put in (("a_mmap", put_np(a)), ("d_pinned_host", put_pinned(d))):
            stop = threading.Event()

            def uploader(put=put):
                while not stop.is_set():
                    jax.block_until_ready(put())

            t = threading.Thread(target=uploader, daemon=True)
            t.start()
            time.sleep(0.2)
            acts[f"beside_{name}"] = small()
            stop.set()
            t.join()
        report["small_transfers"] = acts
        write(report)

        # How many such trees the host will hold pinned (a sweep streams
        # six or seven): stop at the first refusal.
        held, hold_s = [], []
        try:
            for _ in range(args.hold):
                t0 = time.perf_counter()
                held.append(jax.block_until_ready(jax.device_put(b, pinned)))
                hold_s.append(time.perf_counter() - t0)
        except Exception as exc:  # noqa: BLE001 - the refusal is the finding
            report["pinned_hold_error"] = repr(exc)[:300]
        report["pinned_trees_held"] = len(held) + 2
        report["pinned_hold_s"] = hold_s
        del held

        del d, e

    write(report)
    print(json.dumps({k: v for k, v in report.items() if k != "rows"}), flush=True)
    base = rows[0]["alone_gbps"]
    print(f"\nloop alone {loop_alone:.2f} programs/s ({report['program_s'] * 1e3:.1f} ms a program)")
    print("source            alone GB/s  x(a)   beside GB/s  kept   loop/s   kept   rate  overlap")
    for r in rows:
        rate = r["alone_gbps"] >= 1.10 * base
        overlap = r["loop_kept"] >= 0.75 and r["upload_kept"] >= 0.85
        print(
            f"{r['source']:<17} {r['alone_gbps']:>9.2f}  {r['alone_gbps'] / base:>5.2f}  "
            f"{r['beside_gbps']:>10.2f}  {r['upload_kept']:>5.2f}  "
            f"{r['loop_beside_programs_per_s']:>6.2f}  {r['loop_kept']:>5.2f}   "
            f"{'yes' if rate else 'no':<4}  {'yes' if overlap else 'no'}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
