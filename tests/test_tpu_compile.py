"""The Pallas kernels of the counting path compile for a TPU v5e chip.

Each case compiles one kernel, at the shapes the counting path gives it,
for a v5e chip that is described rather than attached: the TPU compiler
refuses here what it would refuse on the chip (block tiling, VMEM, ops
Mosaic cannot lower), and the compiled program must hold the kernel as a
``tpu_custom_call``.  The sparse hop's segment-id program, which gathers
its child codes by rows on a TPU, compiles there within the memory its
steps allow.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.
"""

import functools

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.bdeu_kernel import bdeu_pallas
from repro.kernels.hist_kernel import segment_hist_pallas
from repro.kernels.segsum_kernel import (blocked_segment_sum_pallas,
                                         segment_sum_ones_pallas,
                                         segment_sum_rows_pallas)

I32, F32 = jnp.int32, jnp.float32

# name -> (kernel, static kwargs, argument shapes and dtypes)
CASES = {
    # the dense-message hop at the kernel's segment cap
    "segsum_rows_3000x64_32768seg": (
        segment_sum_rows_pallas, dict(num_segments=32768),
        [((3000,), I32), ((3000, 64), F32)]),
    # a leaf hop over one VisualGenome relationship's 1.9 M edges
    "segsum_ones_1.9M_edges": (
        segment_sum_ones_pallas, dict(num_segments=32768),
        [((1_900_000,), I32), ((1_900_000,), F32)]),
    # a leaf hop over one VisualGenome relationship in its blocked layout:
    # 782 tiles of 256 parents (200 k), padded to 784 rows, of 2688 slots,
    # into 3 x 3 codes
    "blocked_segsum_784x2688_9codes": (
        blocked_segment_sum_pallas, dict(num_codes=9),
        [((784, 2688), I32), ((784, 2688), I32), ((784, 2688), F32)]),
    "segment_hist": (segment_hist_pallas, dict(num_segments=1000),
                     [((5000,), I32), ((5000, 48), F32)]),
    # Q > block_q: several Q-blocks, one partial tile each
    "bdeu_3_qblocks": (bdeu_pallas, dict(ess=1.0), [((1500, 3), F32)]),
}


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
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    kernel, kwargs, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    fn = jax.jit(functools.partial(kernel, interpret=False, **kwargs))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_a_hop_gathers_codes_by_rows_in_bounded_steps(one_chip,
                                                      no_persistent_cache):
    """A VisualGenome hop: 1.9 M edge ids into 200 k entity codes, with one
    edge attribute.  Fetched all at once, the rows would take 973 MB."""
    from repro.core.executors import _ROW_CHUNK, _hop_segment_ids

    edges = jax.ShapeDtypeStruct((1_900_000,), I32, sharding=one_chip)
    code = jax.ShapeDtypeStruct((200_000,), I32, sharding=one_chip)
    compiled = _hop_segment_ids.lower(
        code, edges, edges, (edges,), ds=72, cards=(3,),
        by_rows=True).compile()
    rows = _ROW_CHUNK * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * rows


def test_a_blocked_hop_gathers_slot_codes_in_bounded_steps(
        one_chip, no_persistent_cache):
    """The same hop over its blocked layout: 784 x 3072 slots."""
    from repro.core.executors import _ROW_CHUNK, _hop_slot_codes

    slots = jax.ShapeDtypeStruct((784, 3072), I32, sharding=one_chip)
    code = jax.ShapeDtypeStruct((200_000,), I32, sharding=one_chip)
    compiled = _hop_slot_codes.lower(code, slots, (slots,), cards=(3,),
                                     by_rows=True).compile()
    rows = _ROW_CHUNK * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * rows
