"""Record the small TPU profiler trace that ``test_trace.py`` reads.

It runs a few ticks of a tiny jitted program named ``micro_step`` that
holds an XLA matmul and the program's Pallas kernels (uniconv, group
norm, flash attention), each tick inside a ``bench.engine_step`` host
span, with host-side sleeps between ticks so that the device has idle
gaps.  It writes ``<out>/tiny.xplane.pb`` and a plain-text listing of
the trace's planes, lines and first events beside it.

Usage (on a machine with one TPU chip)::

    PYTHONPATH=src python benchmarks/chip/testdata/record_trace.py --out DIR
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--ticks", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {jax.default_backend()}")
    from repro.models.backend import resolve_backend

    bk = resolve_backend("pallas")

    def micro_step(x, w, w3, p):
        h = bk.group_norm(x, p, 8, silu=True)
        h = bk.conv(w3, None, h, (16, 16), 3)
        q = h @ w
        a = bk.attention(q, q, q, w, 2)
        return x + a

    step = jax.jit(micro_step)
    key = jax.random.key(0)
    x = jax.random.normal(key, (2, 256, 128), jnp.float32)
    w = jax.random.normal(key, (128, 128), jnp.float32) * 0.05
    w3 = jax.random.normal(key, (9, 128, 128), jnp.float32) * 0.05
    p = {"scale": jnp.ones((128,), jnp.float32), "bias": jnp.zeros((128,), jnp.float32)}
    jax.block_until_ready(step(x, w, w3, p))

    os.makedirs(args.out, exist_ok=True)
    logdir = tempfile.mkdtemp(dir=args.out)
    jax.profiler.start_trace(logdir)
    for _ in range(args.ticks):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            x = step(x, w, w3, p)
            jax.block_until_ready(x)
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(args.out, "tiny.xplane.pb")
    shutil.copyfile(path, dst)
    shutil.rmtree(logdir)

    data = jax.profiler.ProfileData.from_file(dst)
    with open(os.path.join(args.out, "tiny.listing.txt"), "w") as f:
        for plane in data.planes:
            f.write(f"PLANE {plane.name!r} stats={list(plane.stats)[:20]}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for ev in evs[:25]:
                    f.write(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} "
                            f"stats={list(ev.stats)[:12]}\n")
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")


if __name__ == "__main__":
    main()
