"""Compile the main path's Pallas programs for a described TPU v5e.

Nothing runs: each test lowers a kernel or a fused round program at a real
row bucket for one chip of a ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode accepts (unaligned slices,
too much VMEM, dynamic indexing into loaded values). Every compiled program
must contain the Pallas kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture, never while a module is
imported, so every test worker collects the same tests and only the worker
that runs this file loads the TPU compiler. The persistent compile cache is
off here: an executable compiled for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_named_kernel(compiled):
    """The Sinkhorn kernel keeps the stable name the benchmark's trace
    reduction finds it by."""
    _assert_kernel(compiled)
    assert "sinkhorn_iteration_pallas" in compiled.as_text()


@pytest.mark.parametrize("rows,cols", [(4096, 5), (4096, 40), (16384, 5),
                                       (16384, 40)])
def test_sinkhorn_iteration_compiles(one_chip, rows, cols):
    from repro.kernels.sinkhorn.sinkhorn import sinkhorn_iteration_pallas

    compiled = sinkhorn_iteration_pallas.lower(
        _shape(one_chip, (rows, cols)), _shape(one_chip, (cols,)),
        _shape(one_chip, (rows,)), _shape(one_chip, (cols,)),
        eps=0.05).compile()
    _assert_kernel(compiled)


def test_assignment_program_compiles(one_chip):
    from repro.core.round import _assignment_program

    bucket, cols = 4096, 6                  # 5 regions + the defer arc
    compiled = _assignment_program.lower(
        _shape(one_chip, (3, bucket - 1, cols)),
        _shape(one_chip, (bucket - 1, 2)),
        _shape(one_chip, (cols,)),
        soften=False, sigma=10.0, impl="pallas", eps_min=0.005,
        interpret=False).compile()
    _assert_named_kernel(compiled)


def test_batched_assignment_program_compiles_over_four_chips(topo):
    """The fleets' batched program: 8 slots at bucket 4096, two on each
    chip of the 2x2 host, vmapped and shard_mapped over the four."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.round import _batch_program

    slots, bucket, cols = 8, 4096, 6
    cells = NamedSharding(Mesh(np.asarray(topo.devices), ("cells",)),
                          P("cells"))
    compiled = _batch_program(
        tuple(topo.devices), soften=False, sigma=10.0, impl="pallas",
        eps_min=0.005, interpret=False).lower(
        _shape(cells, (slots, 3, bucket - 1, cols)),
        _shape(cells, (slots, bucket - 1, 2)),
        _shape(cells, (slots, cols))).compile()
    _assert_named_kernel(compiled)


def test_temporal_program_compiles(one_chip):
    from repro.core import footprint
    from repro.core.round import _temporal_program

    bucket, slots, regions = 4096, 8, 5
    width = 4 + 3 * slots * regions + 2 * regions
    server = footprint.m5_metal()
    compiled = _temporal_program.lower(
        _shape(one_chip, (bucket - 1, width)),
        _shape(one_chip, (4, regions)),
        offsets=tuple(1800.0 * s for s in range(slots)), lam_co2=0.5,
        lam_h2o=0.5, defer_eps=1e-3, guard_s=240.0,
        lifetime_s=float(server.lifetime_s),
        embodied_gco2=float(server.embodied_gco2),
        embodied_water_l=float(server.embodied_water_l), want_plan=False,
        impl="pallas", eps_min=0.005, interpret=False).compile()
    _assert_named_kernel(compiled)


@pytest.mark.parametrize("shape", [(64, 48, 16), (5, 200, 16)])
def test_rglru_scan_compiles(one_chip, shape):
    from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas

    compiled = rglru_scan_pallas.lower(
        _shape(one_chip, shape), _shape(one_chip, shape),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_rglru_scan_gradient_compiles(one_chip):
    """The custom VJP runs the same kernel on time-reversed inputs."""
    from repro.kernels.rglru_scan.ops import rglru_scan

    shape = (64, 48, 16)
    w = np.ones(shape, np.float32)

    def loss(a, bx):
        return jnp.sum(w * rglru_scan(a, bx, interpret=False))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _shape(one_chip, shape), _shape(one_chip, shape)).compile()
    _assert_kernel(compiled)
