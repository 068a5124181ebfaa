"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so it can refuse here what the
chip would refuse (misaligned tiles, too much VMEM, a program that does
not fit) at no chip time.  Nothing here runs on a device.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os
import re
import tempfile
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.clique import make_clique_computation
from repro.core.engine import Engine, EngineConfig
from repro.data.synthetic_graphs import planted_clique_graph
from repro.kernels.masked_intersect import masked_intersect

N_STEP = 8192          # W = 256, clique state width S = 514


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tpu_log_dir(tmp_path_factory):
    """The TPU library writes its compiler logs where ``TPU_LOG_DIR``
    says when it loads, and otherwise under the system temp directory,
    outside the test run; point it at the run's own temp tree."""
    path = tmp_path_factory.mktemp("tpu_logs")
    was = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = str(path)
    yield path
    if was is None:
        del os.environ["TPU_LOG_DIR"]
    else:
        os.environ["TPU_LOG_DIR"] = was


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache, tpu_log_dir):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_tpu_logs_stay_in_the_test_tree(one_chip, tpu_log_dir):
    """Describing the chip loaded the TPU library in this process: its
    logs (named ``*.<pid>``) are in the fixture's directory and nowhere
    under the system temp directories."""
    pid = f".{os.getpid()}"
    assert any(p.name.endswith(pid) for p in tpu_log_dir.iterdir())
    stray = [p for d in {Path("/tmp"), Path("/tmp/tpu_logs"),
                         Path(tempfile.gettempdir())}
             if d.is_dir() for p in d.iterdir()
             if p.name.startswith("tpu_driver") and p.name.endswith(pid)]
    assert not stray


@pytest.fixture(scope="module")
def clique_engine():
    g = planted_clique_graph(n=N_STEP, m=8 * N_STEP, clique_size=12)
    comp = make_clique_computation(g, use_pallas=True, interpret=False)
    return Engine(comp, EngineConfig(k=3, batch=64, pool_capacity=4096,
                                     steps_per_sync=16))


def _step_args(eng, sharding=None):
    def sd(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return ([sd((eng.C, eng.S)), sd((eng.C,)), sd((eng.C,)),
             sd((eng.k, eng.S)), sd((eng.k,))],
            jax.tree.map(lambda a: sd(a.shape, a.dtype), eng.comp.tables))


@pytest.mark.parametrize("with_mask", [False, True])
def test_masked_intersect_compiles_at_full_width(one_chip, with_mask):
    """The kernel's default 8x128 blocks at the widest graph the clique
    layout serves in this repo's cells: N=32768, W=1024 words."""
    b, n, w = 64, 32768, 1024

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    mask = sd((b, w)) if with_mask else None
    fn = jax.jit(lambda a, cols, m: masked_intersect(a, cols, m,
                                                     interpret=False))
    compiled = fn.lower(sd((b, w)), sd((n, w)), mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_clique_step_compiles_for_v5e(one_chip, clique_engine):
    """One kernel-path clique super-step, graph tables as arguments."""
    pool, tables = _step_args(clique_engine, one_chip)
    compiled = clique_engine._step.lower(*pool, tables).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the [N, W] tables are arguments, not constants baked into the code
    table_bytes = N_STEP * (N_STEP // 32) * 4
    assert mem.generated_code_size_in_bytes < table_bytes


def _widest_sort(text: str) -> int:
    return max(len(m.group(1).split(","))
               for m in re.finditer(r'"stablehlo\.sort"\(([^)]*)\)', text))


@pytest.mark.parametrize("program", ["step", "macro"])
def test_step_sorts_stay_narrow(one_chip, clique_engine, program):
    """Compile time of a sort grows with its operand count, so no sort in
    the step may take one operand per state word (S = 514 here)."""
    pool, tables = _step_args(clique_engine, one_chip)
    if program == "step":
        lowered = clique_engine._step.lower(*pool, tables)
    else:
        lowered = clique_engine._macro.lower(
            *pool, tables, np.int32(16), False, np.int32(0))
    assert _widest_sort(lowered.as_text()) <= 3
