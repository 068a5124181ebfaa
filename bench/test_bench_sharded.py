"""The four-chip cell's run on four CPU devices: correct as it stands,
and not correct with the exchange of results between chips left out.

Four devices must be fixed before JAX starts, so the run is a child
process of its own."""
import json
import os
import subprocess
import sys

from bench import harness

CHILD = r"""
import json, sys, time
from bench import harness
from bench.test_bench_run import small_cell
from repro.distributed import sharded_engine

def run():
    return harness.run_cell(small_cell("mico-wclique-x4.c4", n=256),
                            2 ** 31 + 17, 0.5, False, time.perf_counter(),
                            require_tpu=False)

sound = run()
merge = sharded_engine.merge_topk

def shard_zero_only(states, keys, k):
    # each shard's local top-k is a block of k rows; keep shard 0's
    return merge(states[:k], keys[:k], k)

sharded_engine.merge_topk = shard_zero_only
broken = run()
print(json.dumps(dict(sound=sound, broken=broken)))
"""


def test_sharded_cell_and_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [harness.ROOT, os.path.join(harness.ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=harness.ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"]["device"]["count"] == 4
    assert res["sound"]["correct"] is True
    assert res["broken"]["correct"] is False
    assert res["broken"]["limits"]["mismatched"]["value"] > 0
