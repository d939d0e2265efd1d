"""Records the small chip trace that ``test_trace_reduce.py`` reads.

    chiprun -- python benchmarks/tests/record_trace.py chiprun_out/trace_small

Run on the chip, by hand, when the recorded trace has to be made anew: a
few harness-named spans around a small jitted stencil loop, with host
sleeps between them so that the trace holds idle gaps of known cause.
Writes the raw ``.xplane.pb`` next to ``rows.json`` (what
``trace_reduce.read_planes`` makes of it) and ``describe.json`` (planes,
lines and the first events of each, for the look by hand).
"""

import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import trace_reduce

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")

    def bench_small_stencil(x):
        def body(_, y):
            return y + 0.1 * (jnp.roll(y, 1, 0) + jnp.roll(y, -1, 0)
                              + jnp.roll(y, 1, 1) + jnp.roll(y, -1, 1)
                              - 4.0 * y)
        return jax.lax.fori_loop(0, 20, body, x)

    fn = jax.jit(bench_small_stencil)
    x = jnp.ones((256, 256, 128), jnp.float32)
    jax.block_until_ready(fn(x))
    tdir = os.path.join(out, "raw")
    trace_reduce.start(tdir)
    with jax.profiler.TraceAnnotation("bench:traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:advance"):
                x = fn(x)
                jax.block_until_ready(x)
            with jax.profiler.TraceAnnotation("bench:host_sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    devices, host = trace_reduce.read_planes(path)
    host = [r for r in host if r[2].startswith("bench:")]
    with open(os.path.join(out, "rows.json"), "w") as f:
        json.dump({"devices": devices, "host": host}, f)
    with open(os.path.join(out, "describe.json"), "w") as f:
        json.dump(trace_reduce.describe(path), f, indent=1)
    red = trace_reduce.reduce(devices, host, "bench:traced_window", "bench:",
                              ["bench_small_stencil"])
    print(json.dumps(red, indent=1))
    print("xplane bytes", os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
