"""Chip probe (run by hand through the chip tool): what the machine is, how
fast its link, disk and checksum are, and a tiny recorded trace that holds a
Pallas attention call, a matmul and a benchmark span. Writes
``chiprun_out/probe.json`` and ``chiprun_out/probe.xplane.pb``."""

import glob
import json
import os
import shutil
import sys
import time
import zlib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def main():
    out = {"cpu_count": os.cpu_count(), "env": {k: os.environ.get(k) for k in (
        "JAX_COMPILATION_CACHE_DIR", "TMPDIR", "HOME", "XDG_CACHE_HOME", "JAX_PLATFORMS")}}
    with open("/proc/meminfo") as f:
        out["meminfo"] = {l.split(":")[0]: l.split()[1] for l in f.readlines()[:3]}
    out["df"] = os.popen("df -h . /tmp /dev/shm 2>&1").read()
    import jax
    import jax.numpy as jnp

    d = jax.devices()[0]
    out["device"] = {"platform": d.platform, "kind": d.device_kind, "n": len(jax.devices()),
                     "memory_stats": {k: v for k, v in (d.memory_stats() or {}).items()}}
    # host->HBM, HBM->host
    buf = np.ones((256, 1024, 256), np.float32)
    a = jax.device_put(buf, d); jax.device_get(a.sum())
    t0 = time.perf_counter(); a = jax.device_put(buf, d); jax.device_get(a.sum())
    out["h2d_gbps"] = buf.nbytes / 1e9 / (time.perf_counter() - t0)
    t0 = time.perf_counter(); _ = np.asarray(a)
    out["d2h_gbps"] = buf.nbytes / 1e9 / (time.perf_counter() - t0)
    # generate 1 GB bf16 on device
    g = jax.jit(lambda k: (jax.random.normal(k, (64, 2048, 1408), jnp.float32) * 0.02).astype(jnp.bfloat16))
    g(jax.random.PRNGKey(0)).block_until_ready()
    t0 = time.perf_counter(); w = g(jax.random.PRNGKey(1)); w.block_until_ready()
    out["gen_369MB_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); wn = np.asarray(w); out["fetch_369MB_s"] = time.perf_counter() - t0
    # disk write + crc
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    p = os.path.join(ROOT, ".bench_work", "probe.bin")
    raw = wn.view(np.uint8).reshape(-1)
    t0 = time.perf_counter()
    with open(p, "wb") as f:
        f.write(raw.data)
    out["write_369MB_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); zlib.crc32(raw.data); out["crc_369MB_s"] = time.perf_counter() - t0
    os.remove(p)
    # tiny trace
    from flexible_llm_sharding_tpu.ops import pallas_attention as pa

    b, h, lp, s, ls, dq = 1, 4, 256, 2, 64, 192
    qs = jnp.ones((b, s, ls, h, dq), jnp.bfloat16)
    mm = jax.jit(lambda x: x @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    mm(x).block_until_ready()
    import inspect
    out["prefix_shared_sig"] = str(inspect.signature(pa.flash_prefix_shared_attention))
    out["causal_sig"] = str(inspect.signature(pa.flash_causal_attention))
    q = jnp.ones((lp, h, dq), jnp.bfloat16)
    fc = jax.jit(lambda q: pa.flash_causal_attention(q, q, q[..., :128], jnp.int32(lp), scale=0.07))
    try:
        fc(q).block_until_ready()
        ok = True
    except Exception as e:  # noqa: BLE001
        out["causal_error"] = repr(e)[:500]
        ok = False
    tdir = os.path.join(ROOT, ".bench_work", "probe_trace")
    opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0; opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.batch.run"):
            y = mm(x)
            if ok:
                y2 = fc(q)
                y2.block_until_ready()
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.batch.prepare"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    xp = glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    shutil.copy(xp, os.path.join(ROOT, "chiprun_out", "probe.xplane.pb"))
    out["xplane_bytes"] = os.path.getsize(xp)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xp)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "n": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns, "dur_ns": e.duration_ns,
                 "stats": {k: str(v)[:120] for k, v in list(dict(e.stats).items())[:12]}}
                for e in evs[:8]]})
        planes.append({"plane": plane.name, "lines": lines})
    out["planes"] = planes
    shutil.rmtree(os.path.join(ROOT, ".bench_work"), ignore_errors=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "planes"}, indent=1))


if __name__ == "__main__":
    main()
