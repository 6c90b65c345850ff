"""Compile the chip's main-path programs for a described TPU v5e (one
chip, or the four of a 2x2 host for the sharded run loop).

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse (an
op it cannot lower, a kernel Mosaic rejects, a program that does not fit
the device's memory).  The topology is described inside a fixture, never
at import, so every test worker collects the same tests and only the one
given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core.hext import engine, programs
from repro.core.hext.bits import x64
from repro.core.hext.sim import Fleet
from repro.kernels.pagewalk.kernel import two_stage_translate_kernel

V5E_HBM_BYTES = 16 * 1000 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), (engine.HARTS,))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_run_loop_compiles_for_v5e_and_fits(one_chip):
    """The donated while-loop over ``step_batched`` for the paper's
    18-hart native+guest matrix, as ``Fleet.run`` calls it."""
    wls = programs.WORKLOADS
    fleet = Fleet.boot(wls + wls, guest=[False] * len(wls) + [True] * len(wls))
    with x64():
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            fleet.harts.unwrap())
        n_chunks = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = engine._run_jit_donating.lower(
            shapes, n_chunks, 8192, 1).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_sharded_run_loop_compiles_for_v5e_2x2_without_collectives(mesh):
    """``ShardedEngine``'s donated ``shard_map`` loop for 4 copies of the
    matrix, 18 harts a chip of a 2x2 v5e, as ``Fleet.run`` calls it: each
    chip exits its own loop, so the program holds no collective, and the
    hart state is updated in place."""
    wls = programs.WORKLOADS
    fleet = Fleet.boot(wls + wls, guest=[False] * len(wls) + [True] * len(wls))
    harts = NamedSharding(mesh, PartitionSpec(engine.HARTS))
    with x64():
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((4 * x.shape[0],) + x.shape[1:],
                                           x.dtype, sharding=harts),
            fleet.harts.unwrap())
        n_chunks = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec()))
        compiled = engine._run_sharded.lower(
            shapes, n_chunks, mesh, 8192, 1).compile()
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in text, op
    m = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(shapes)) // 4
    assert m.alias_size_in_bytes >= state_bytes
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("T,R,P,G,B", [
    (8, 64, 512, 512, 4096),     # the kernel docstring's working size
    (3, 4, 16, 32, 513),         # tiny tables, a ragged last query block
])
def test_pagewalk_kernel_compiles_for_v5e(one_chip, T, R, P, G, B):
    """``ops.two_stage_translate`` routes ``auto`` to this kernel on a TPU;
    Mosaic must accept it with ``interpret=False``."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = two_stage_translate_kernel.lower(
        s((T, R, P), jnp.int32), s((T, R, P), jnp.int32),
        s((T, G), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.bool_), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
