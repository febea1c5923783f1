"""Record ``fixtures/fixture.xplane.pb``, the trace that
``test_bench_trace.py`` reduces.  Needs a TPU:

    python bench/tests/record_trace_fixture.py <out_dir>

Five executions of one jitted program (a matmul, then a small Pallas
kernel named ``double_kernel``), each inside a ``bench.step``
annotation, with a 50 ms ``bench.idle`` sleep after each.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

STEPS = 5
SLEEP_S = 0.05


def double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def fixture_step(x):
    y = jnp.tanh(x @ x)
    return pl.pallas_call(
        double_kernel, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype))(y)


def main(out_dir: str) -> None:
    x = jnp.ones((1024, 1024), jnp.float32)
    fixture_step(x).block_until_ready()          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench.step"):
            fixture_step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    for root, _, files in os.walk(out_dir):
        for f in files:
            print(os.path.join(root, f), os.path.getsize(os.path.join(root, f)))


if __name__ == "__main__":
    main(sys.argv[1])
