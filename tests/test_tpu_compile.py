"""Compile rehearsals for a described TPU v5e: the dispatch path's
kernels and replay programs at real widths must pass the chip's
compiler.  Nothing runs — these compile for a 2x2 v5e topology that is
described, not attached, so they say nothing about results or times.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler; where it cannot be described the tests skip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.control_unit import CMD_WIDTH, chip_replay, faulty_chip_replay
from repro.core.ops_library import get_op
from repro.distributed.pum import make_chip_executor
from repro.kernels.bitplane_ops import circuit_on_planes
from repro.kernels.transpose_kernel import h2v_pallas, v2h_pallas

# the TPC-H Q6 SF1 round: 16 banks x 1 subarray, 128-row slabs of
# 16,384 words (6,001,215 rows over 16 chains), 512-command tables
BANKS, SUBS, ROWS, WORDS, CMDS = 16, 1, 128, 16_384, 512
LANES = 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _replay_args(sharding):
    return (_sds((BANKS, SUBS, ROWS, WORDS), jnp.uint32, sharding),
            _sds((BANKS, SUBS, CMDS, CMD_WIDTH), jnp.int32, sharding))


def test_chip_replay_compiles_at_sf1_shape(one_chip):
    compiled = jax.jit(chip_replay).lower(*_replay_args(one_chip)).compile()
    state_bytes = BANKS * SUBS * ROWS * WORDS * 4
    assert compiled.memory_analysis().output_size_in_bytes >= state_bytes


def test_faulty_chip_replay_compiles_at_sf1_shape(one_chip):
    units = (BANKS, SUBS)
    args = _replay_args(one_chip) + (
        _sds(units + (2,), jnp.uint32, one_chip),          # keys
        _sds(units + (WORDS,), jnp.uint32, one_chip),      # stuck0
        _sds(units + (WORDS,), jnp.uint32, one_chip),      # stuck1
        _sds(units, jnp.bool_, one_chip),                  # dead
        _sds((), jnp.float32, one_chip),                   # p_flip
    )
    jax.jit(faulty_chip_replay).lower(*args).compile()


@pytest.mark.parametrize("direction", ["h2v", "v2h"])
def test_transpose_kernel_compiles_to_mosaic(one_chip, direction):
    if direction == "h2v":
        fn = lambda v: h2v_pallas(v, interpret=False)
        arg = _sds((LANES,), jnp.uint32, one_chip)
    else:
        fn = lambda p: v2h_pallas(p, interpret=False)
        arg = _sds((32, LANES // 32), jnp.uint32, one_chip)
    compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_circuit_kernel_compiles_to_mosaic(one_chip):
    spec = get_op("addition", 8)
    circ, ids = spec.build("mig")
    words = LANES // 32
    args = [_sds((w, words), jnp.uint32, one_chip) for w in spec.operand_bits]
    fn = lambda *planes: circuit_on_planes(circ, ids, list(planes),
                                           interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_chip_executor_compiles_on_four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    executor = make_chip_executor(BANKS, mesh=mesh, use_shard_map=True)
    assert executor.sharded
    banks = NamedSharding(mesh, P("data", None, None, None))
    compiled = executor.run.lower(*_replay_args(banks)).compile()
    per_device = BANKS // 4 * SUBS * ROWS * WORDS * 4
    assert compiled.memory_analysis().output_size_in_bytes == per_device
