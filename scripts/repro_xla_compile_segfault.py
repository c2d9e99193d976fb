"""Minimal repro for the XLA:CPU many-compilations segfault that
tests/conftest.py's per-module ``jax.clear_caches()`` fixture works around
(VERDICT r3 weak #7: the workaround was undiagnosed).

The full test suite accumulates 300+ distinct XLA:CPU executables in one
process and segfaults inside ``backend_compile_and_load`` at ~94% of the
run; any individual module passes. This script isolates the variable: it
compiles N distinct tiny programs (distinct static shapes -> distinct
executables) in one process and reports how far it gets.

Modes:
  keep   — hold every compiled function alive (the suite's behaviour
           without the fixture; session-scoped fixtures + module globals
           pin executables for the process lifetime)
  drop   — drop references immediately (executables become collectable;
           jit cache still holds them until clear)
  clear  — hold references but ``jax.clear_caches()`` every --clear-every
           compiles (the conftest mitigation)
  suite  — suite-shaped programs instead of tiny matmuls: vmapped
           scan-over-stacked-layers bodies with donated carries compiled
           against the 8-virtual-device CPU backend, cycling shapes like
           the per-module model configs do (refs held, no clears)

RESULT (2026-07-31, this rig): `keep` survives 800 tiny distinct-shape
compiles with every executable live; `suite` survives 400 scan/vmap/donated
compiles against the 8-virtual-device backend with refs held. Neither
executable COUNT nor program SHAPE reproduces the crash in isolation — the
full suite's state is required (its much larger per-program code size,
cross-module config/fixture mix, and spawned-subprocess modules are the
remaining deltas; the crash site, XLA:CPU ``backend_compile_and_load``, and
this host's cpu_aot_loader machine-feature-mismatch warnings point at the
compile/load path, not execution). Diagnosis of record: a cumulative
compile-path resource, not a countable executable limit; the conftest
per-module ``jax.clear_caches()`` bounds that resource and remains the
mitigation. Confirmed fresh on this tree (2026-07-31):
``FLS_NO_CLEAR_CACHES=1 python -m pytest tests/ -q`` → SIGSEGV (rc 139)
at ~92% with the faulting thread inside
``jax/_src/compiler.py:362 backend_compile_and_load`` during a pjit
compile, while the same tree with the mitigation passes 342/342. That
one-liner IS the minimal known repro.

Usage: python scripts/repro_xla_compile_segfault.py [keep|drop|clear|suite]
           [--n 800] [--clear-every 60]
A segfault prints nothing — run under ``bash -c '...; echo rc=$?'`` and
read the exit code (139 = SIGSEGV).
"""

import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] = (
        os.environ["XLA_FLAGS"] + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import jax.numpy as jnp

# This repro is about XLA:CPU: pin the CPU platform before any backend init,
# whatever the environment preset.
jax.config.update("jax_platforms", "cpu")


def _suite_compile(i: int):
    """One suite-shaped compilation: vmapped scan over a stacked 2-layer
    pytree with a donated carry — the structure of executor._decoder_block,
    at a shape cycled by ``i`` like the per-module model configs."""
    import functools

    d = 32 + 4 * (i % 40)  # cycle hidden sizes
    k, b, l = 2, 2, 6 + (i // 40)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def block(stack, h):
        def body(c, lp):
            c = jnp.tanh(c @ lp["w"]) + c * lp["g"][None, None, :]
            return c, None

        def one(hh):
            out, _ = jax.lax.scan(body, hh[None], stack)
            return out[0]

        return jax.vmap(one)(h)

    stack = {
        "w": jnp.ones((k, d, d), jnp.float32) * 0.01,
        "g": jnp.ones((k, d), jnp.float32),
    }
    h = jnp.ones((b, l, d), jnp.float32)
    block(stack, h).block_until_ready()
    return block


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("mode", choices=["keep", "drop", "clear", "suite"],
                   default="keep", nargs="?")
    p.add_argument("--n", type=int, default=800)
    p.add_argument("--clear-every", type=int, default=60)
    args = p.parse_args()

    kept = []
    for i in range(args.n):
        if args.mode == "suite":
            kept.append(_suite_compile(i))
        else:
            n = 4 + i  # distinct shape -> distinct compilation, like the
            # suite's per-module model configs

            def f(x, c=n):
                return (x @ x + c).sum()

            jf = jax.jit(f)
            jf(jnp.ones((n, n), jnp.float32)).block_until_ready()
            if args.mode in ("keep", "clear"):
                kept.append(jf)  # clear mode holds refs too — isolating
                # clear_caches() itself as the curative variable
            if args.mode == "clear" and (i + 1) % args.clear_every == 0:
                kept.clear()
                jax.clear_caches()
        if (i + 1) % 50 == 0:
            print(f"{i + 1} compiles ok", flush=True)
    print(f"done: {args.n} compiles survived in mode={args.mode}",
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
