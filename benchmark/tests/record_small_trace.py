#!/usr/bin/env python3
"""How `recorded_v5e.xplane.pb` was made (PR 22, one TPU v5e chip):

    python benchmark/tests/record_small_trace.py <output directory>

Three `job` annotations, each a `load` annotation that sleeps 20 ms and then
two launches of one small jitted program, captured with the Python tracer
off as `serve.quiet_python_tracer` has it."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def bench_probe_program(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) + 1.0
    return x.sum()


def main(out_dir: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    x = jnp.ones((1024, 1024), jnp.float32)
    bench_probe_program(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    work = os.path.join(out_dir, "capture")
    jax.profiler.start_trace(work, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("job"):
            with jax.profiler.TraceAnnotation("load"):
                time.sleep(0.02)
            bench_probe_program(x).block_until_ready()
            bench_probe_program(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(work, "plugins/profile/*/*.xplane.pb"))
    shutil.copy(found, os.path.join(out_dir, "recorded_v5e.xplane.pb"))
    shutil.rmtree(work)
    print(os.path.getsize(os.path.join(out_dir, "recorded_v5e.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
