#!/usr/bin/env python3
"""Record the small real traces that ``test_trace_reduce.py`` reads, on the
chip:  python benchmark/tests/record_trace_fixture.py <chips> <out.xplane.pb>

A few steps of a small matmul on every chip, summed across chips where there
are several, inside the benchmark's own ``bench/window`` and ``bench/step``
annotations, with a pause between steps so that the device has idle gaps.
"""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(chips: int, out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import trace_reduce

    devices = jax.devices()[:chips]
    assert devices[0].platform == "tpu" and len(devices) == chips, devices
    mesh = Mesh(np.asarray(devices), ("data",))
    x = jax.device_put(jnp.ones((chips * 256, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return y, jnp.sum(y.astype(jnp.float32))  # all-reduce across chips

    jax.block_until_ready(step(x, w))
    trace_dir = os.path.join(os.path.dirname(out) or ".", "_fixture_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with trace_reduce.tracing(trace_dir) as session:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                jax.block_until_ready(step(x, w))
            with jax.profiler.TraceAnnotation("bench/pause"):
                time.sleep(0.002)
    shutil.copy(trace_reduce.find_xplane(trace_dir), out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    reduced = trace_reduce.reduce_file(out, chips, session.host_window_s)
    print(chips, "chips:", os.path.getsize(out), "bytes; window",
          reduced.window_s, "busy", reduced.busy_s, reduced.breakdown())


if __name__ == "__main__":
    main(int(sys.argv[1]), sys.argv[2])
