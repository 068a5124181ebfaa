#!/usr/bin/env python3
"""Record the small device trace that ``test_bench_trace.py`` reads.

    python3 bench/testdata/record_trace.py [OUT_DIR]

On a TPU, traces a window of three rounds of a matrix product and a sort,
with a host sleep between them, and writes the profiler's ``.xplane.pb``
and its Chrome-format ``.trace.json.gz`` as ``small.xplane.pb`` and
``small.trace.json.gz`` in ``OUT_DIR`` (this directory by default).
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    matmul = jax.jit(lambda a: a @ a)
    sort = jax.jit(jnp.sort)
    a = jnp.ones((2048, 2048), jnp.float32)
    v = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    matmul(a).block_until_ready()
    sort(v).block_until_ready()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            matmul(a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.005)
            sort(v).block_until_ready()
    jax.profiler.stop_trace()
    dest = sys.argv[1] if len(sys.argv) > 1 else HERE
    os.makedirs(dest, exist_ok=True)
    for pattern, name in (("*.xplane.pb", "small.xplane.pb"),
                          ("*.trace.json.gz", "small.trace.json.gz")):
        src = glob.glob(os.path.join(out, "**", pattern), recursive=True)[0]
        shutil.copy(src, os.path.join(dest, name))
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
