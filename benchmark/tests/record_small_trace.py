#!/usr/bin/env python3
"""Record the small trace the reducer's test reads, on four chips.

    chiprun --chips 4 -- python3 benchmark/tests/record_small_trace.py chiprun_out/small

A toy data-parallel step (two matrix products on a batch sharded over four
chips, the mean of the gradient all-reduced by GSPMD) runs eight times under
the profiler, with the benchmark's host annotations around each call. The
`.xplane.pb` it leaves was copied to `small_dp4.xplane.pb` beside this file.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out_dir: str) -> None:
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("data",))
    batch = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def loss(w, x):
        return jnp.mean(jnp.square(jnp.tanh(x @ w) @ w.T))

    @jax.jit
    def loop(w, x):
        g = jax.grad(loss)(w, x)
        return w - 0.1 * g

    w = jax.device_put(jnp.ones((1024, 1024), jnp.float32) * 0.01, repl)
    x = jax.device_put(jnp.ones((4096, 1024), jnp.float32), batch)
    w = loop(w, x)
    w.block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for _ in range(8):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            w = loop(w, x)
        with jax.profiler.TraceAnnotation("bench.data_wait"):
            time.sleep(0.002)
    w.block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
