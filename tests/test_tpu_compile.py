"""Ahead-of-time compiles of the served path for a TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached: it refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, kernels over the VMEM budget, programs past
the device's memory). Each test lowers one kernel or program of the main
path with ``ShapeDtypeStruct`` inputs placed on a described v5e chip and
compiles it. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
The persistent compilation cache is off around these compiles (a described
chip's executables cannot be read back without one).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

T = 230  # the paper's 10 x 23 profiling grid


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    """Shapes (with the chip's sharding) of every array leaf of ``tree``."""
    return jax.tree_util.tree_map(
        lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("B", [256, 300])  # whole 128-row blocks; ragged tail
def test_pair_scatter_compiles_for_v5e(one_chip, B):
    from repro.kernels.telemetry import pair_scatter

    fn = jax.jit(lambda t, c, v: pair_scatter(t, c, v, interpret=False))
    compiled = fn.lower(_spec(one_chip, (B,), jnp.int32),
                        _spec(one_chip, (B, T)),
                        _spec(one_chip, (2, B))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("m,Q", [(1024, 1), (64, 16), (256, 320)])
def test_consolidation_scores_compiles_for_v5e(one_chip, m, Q):
    from repro.kernels.consolidation import consolidation_scores

    fn = jax.jit(lambda *a: consolidation_scores(*a, interpret=False))
    compiled = fn.lower(_spec(one_chip, (m, T)), _spec(one_chip, (m, T, T)),
                        _spec(one_chip, (T,)), _spec(one_chip, (m, T)),
                        _spec(one_chip, (m,)),
                        _spec(one_chip, (Q,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_closed_loop_compiles_for_v5e(one_chip):
    """The fused multi-segment loop of ``AdaptiveEngine.run(device_loop=True)``
    for a 64-server M1/M2 fleet under its fleet controller, packed by the
    engine itself; about 16 MB of arguments on the chip."""
    from repro.core import M1, M2, AdaptiveEngine, Workload, snap_to_grid
    from repro.core.closed_loop import run_closed_loop
    from repro.core.workload import FS_GRID, RS_GRID
    from repro.fleet import FleetController

    m, segments, n_seg = 64, 4, 32
    servers = [dataclasses.replace([M1, M2][i % 2], name=f"s{i}")
               for i in range(m)]
    rng = np.random.default_rng(0)
    arrivals = [(float(t), snap_to_grid(Workload(fs=float(fs), rs=float(rs))))
                for t, fs, rs in zip(
                    np.cumsum(rng.exponential(5e-5, segments * n_seg)),
                    rng.choice(FS_GRID[14:19], segments * n_seg),
                    rng.choice(RS_GRID[4:], segments * n_seg))]
    engine = AdaptiveEngine(servers, prior=0.0, fleet=FleetController())
    packed = engine._pack_device_loop(arrivals, segments)
    compiled = jax.jit(run_closed_loop, static_argnames=("config",)).lower(
        *_on(one_chip, tuple(packed[:6])), config=packed.config).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < 2**30
