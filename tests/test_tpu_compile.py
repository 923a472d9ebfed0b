"""The store's main-path programs compile for one TPU v5e chip.

Compiled for a described (not attached) chip, so they guard every change
at no chip time: the TPU compiler refuses what interpret mode and the
CPU backend let through, and `memory_analysis()` says what the program
needs of the chip's 16 GiB of HBM. The CPU backend also ignores buffer
donation, which the TPU enforces: every maintenance program must alias
its output state onto the donated input, or the chip holds two states
(at the one-chip geometry the deepest compaction then no longer fits).

The geometry is `bench_params` (the paper's Section 3 ratios at
sizes that compile in seconds), with two disk tiers like the one-chip
geometry (`configs.slsm_paper.one_chip_params`). The full-size compiles
take minutes each; their numbers are recorded in CHANGES.md.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and test workers import every file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.bench.scenarios import bench_params
from repro.engine import compaction as CP
from repro.engine import memtable as MT
from repro.engine import read_path as RP
from repro.engine import tape as TP

HBM_BYTES = 16 * 2**30          # one TPU v5e chip
P = bench_params(max_levels=2)
LEVELS = P.max_levels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def state(one_chip):
    """Abstract full-depth state pytree placed on the described chip."""
    shapes = jax.eval_shape(lambda: MT.init_state(P, LEVELS))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _nbytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _fits(lowered):
    ma = lowered.compile().memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert peak <= HBM_BYTES, peak
    return ma


MAINTENANCE = {
    "stage": lambda s, c: MT.stage_append.lower(
        P, s, _i32(c, P.Rn), _i32(c, P.Rn), _i32(c, P.Rn), _i32(c)),
    "seal": lambda s, c: MT.seal_run.lower(P, s),
    "flush": lambda s, c: CP.merge_buffer_to_level0.lower(P, s, False),
    "spill": lambda s, c: CP.merge_level_down.lower(
        P, s, 0, P.disk_runs_merged, True),
    "compact": lambda s, c: CP.compact_last_level.lower(P, s),
    "tape": lambda s, c: TP.tape_exec.lower(
        P, s, _i32(c, 16), _i32(c, 16, P.Rn), _i32(c, 16, P.Rn),
        _i32(c, 16, P.Rn), _i32(c, 16), False, False),
}


@pytest.mark.parametrize("name", sorted(MAINTENANCE))
def test_state_program_fits_and_aliases_its_state(name, state, one_chip):
    """Each program that rewrites the state fits one chip and writes its
    output state over the donated input."""
    ma = _fits(MAINTENANCE[name](state, one_chip))
    covered = _nbytes(state)
    if name == "compact":
        # the compaction builds the deepest filters afresh and never
        # reads the old ones: those input buffers are dropped, not aliased
        covered -= _nbytes(state.levels[-1].blooms)
    assert ma.alias_size_in_bytes >= covered, (ma.alias_size_in_bytes,
                                               covered)


READS = {
    "lookup_many": lambda s, c: RP.lookup_many.lower(
        P, s, _i32(c, 4096), _i32(c), False, False),
    "range_many": lambda s, c: RP.range_many.lower(
        P, s, _i32(c, 32), _i32(c, 32), _i32(c)),
    "aggregate_many": lambda s, c: RP.aggregate_many.lower(
        P, s, _i32(c, 32), _i32(c, 32), _i32(c)),
}


@pytest.mark.parametrize("name", sorted(READS))
def test_read_program_fits(name, state, one_chip):
    _fits(READS[name](state, one_chip))
