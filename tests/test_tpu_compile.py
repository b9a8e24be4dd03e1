"""The main path's Pallas kernels compile for a TPU v5e, at real sizes.

Interpret mode (every other kernel test) proves the kernels exact but
nothing about Mosaic: unaligned blocks, i1 <-> i8 casts and scoped-VMEM
overflows only show when the chip's compiler sees the kernel. These tests
compile against a *described* ``v5e:2x2`` topology, so they need the TPU
compiler (libtpu) but no chip, and run nothing.

The topology is described inside a module fixture, never at import time:
only the pytest worker that runs this file loads libtpu, and where it
cannot be loaded the tests skip from the fixture. The persistent
compilation cache is off around the compiles: an entry written for a
described chip cannot be read back without one.
"""

import functools

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ccl, denoise, ingest, ychg_fused


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


CASES = {
    # the fused yCHG kernel at a full serving batch of the top ladder rung
    "fused_full_column": (ychg_fused.fused_analyze_pallas, (8, 1024, 1024)),
    # the H-streamed variant on a MODIS L1B 250 m granule
    "fused_streamed": (ychg_fused.fused_analyze_streamed, (1, 8120, 5416)),
    # the whole-image kernels at the same serving batch
    "ccl": (ccl.labels_pallas, (8, 1024, 1024)),
    "denoise": (denoise.denoise_pallas, (8, 1024, 1024)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shape = CASES[case]
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


NAMED = {
    # the kernel's name heads its custom-call instruction, and with it the
    # kernel's event on the XLA Ops line of a chip trace
    "ychg_fused_full": (ychg_fused.fused_analyze_pallas, (4, 256, 5416)),
    "ychg_fused_streamed": (ychg_fused.fused_analyze_streamed,
                            (1, 8120, 5416)),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_fused_kernels_carry_their_names_on_v5e(one_chip, name):
    fn, shape = NAMED[name]
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    lowered = jax.jit(functools.partial(fn, interpret=False)).lower(x)
    text = lowered.compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert len(calls) == 1 and calls[0].startswith(f"%{name}")


@pytest.mark.parametrize("shape, batch", [((21000, 5250), 1),
                                          ((1024, 1354), 4),
                                          ((8120, 1354), 1)])
def test_ingest_unpack_reads_the_words_in_place_on_v5e(one_chip, shape,
                                                       batch):
    """Words of the 21000^2 scene, of a bulk stack of four 256-row strips
    5416 wide and of a whole granule, in the layout the runtime gives
    them, unpack in one named kernel that reads them through a bitcast:
    no copy of the words, no temporary."""
    x = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        ingest.unpack_words, batch=batch,
        interpret=False)).lower(x).compile()
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert len(calls) == 1 and calls[0].startswith("%ingest_unpack")
    assert "copy(%words" not in text and "bitcast(%words" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_ccl_band_path_compiles_for_v5e_at_a_mod44w_tile(one_chip):
    """A 4800x4800 MOD44W tile is past the whole-image block, so
    ``labels_pallas`` takes the band path: the band kernel twice, named
    ``ccl_band_labels`` (labels) and ``ccl_rank_spread`` (ranks), on bands
    of 600 rows 4864 lanes wide, and the whole program (merge and ranking
    included) needs well under 2 GB."""
    x = jax.ShapeDtypeStruct((1, 4800, 4800), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        ccl.labels_pallas, interpret=False)).lower(x).compile()
    calls = sorted(line.strip().removeprefix("ROOT ")
                   for line in compiled.as_text().splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in line)
    assert [c.split(" ")[0].split(".")[0] for c in calls] == [
        "%ccl_band_labels", "%ccl_rank_spread"]
    assert all("s32[1,4800,4864]" in c for c in calls)
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.output_size_in_bytes
            + mem.argument_size_in_bytes) < 2 << 30
