#!/usr/bin/env python3
"""Records the small device trace that test_trace_reduce.py reads.

    python benchmarks/tests/record_small_trace.py <out.xplane.pb>

Three runs each of two jitted programs with stable names, a pause
between the second and the third. Run on the chip (the committed
benchmarks/tests/data/small_tpu.xplane.pb was recorded on a TPU v5e in
PR 24); on a CPU the trace has no device plane.
"""

import os
import shutil
import sys
import tempfile
import time


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmarks import trace_reduce

    @jax.jit
    def bench_trace_probe_sum(x):
        return (x * 2 + 1).sum()

    @jax.jit
    def bench_trace_probe_sort(x):
        return jnp.sort(x)[:8]

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    bench_trace_probe_sum(x).block_until_ready()
    bench_trace_probe_sort(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="small-trace-")
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=po)
    for i in range(3):
        if i == 2:
            time.sleep(0.05)
        bench_trace_probe_sum(x).block_until_ready()
        bench_trace_probe_sort(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
    shutil.copy(path, argv[1])
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(plane.name, [(ln.name, len(list(ln.events)))
                           for ln in plane.lines])
    print(trace_reduce.reduce(argv[1]))
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
