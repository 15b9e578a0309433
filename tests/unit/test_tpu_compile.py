"""The main path's TPU programs, compiled for a DESCRIBED v5e — no chip.

On the CPU every router takes its ``interpret=True`` branch, so the rest
of tier-1 never sees the programs a TPU runs: the fused Pallas bigint and
SHA-256 kernels inside ``lax.scan`` ladders inside ``jax.jit``.  These
tests hand the jitted functions themselves to the chip's own compiler at
the real widths (``scripts/tpu_compile_rehearsal.py`` is the full list, at
``chip_smoke.py``'s shapes).  What the compiler refuses here — a slice
off the tiling, too much fast memory, a program over the device's HBM —
costs no chip time.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
Keep every such test in THIS file — a second file can land on another
worker, where its fixture would skip every test in silence.
"""

import os
import sys

import pytest

import jax

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "scripts",
))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def programs(one_chip):
    """The rehearsal script's program list (the one copy of the shapes),
    built after the topology fixture like everything compiled-mode."""
    import tpu_compile_rehearsal as R

    return {
        name: (fn, args, static)
        for name, fn, args, static in R.single_device_programs(1 << 20, 64)
    }


@pytest.mark.parametrize("name", [
    "hash_blocks_pallas",
    "plane_mul_mod", "plane_add_mod", "plane_sub_mod",
    "merkle_tree_jnp[depth=17]",  # the 2^20-entry balances subtree
    # the 2^20-validator registry subtree: ~9 s alone, over 10 s under load
    pytest.param("merkle_tree_jnp[depth=19]", marks=pytest.mark.slow),
    # one gossip drain: 1,024 entries in 1,024 lanes
    "chain_ladder_g1[gossip b=1024]",
    "chain_norm_g1[gossip c=1 m1=127]",  # its 64 message groups
    # prep's gathers over the ladders' outputs: the padded slots' index b
    # is out of range and filled with the identity
    "chain_take_g1[gossip b=1024 c=1 m1=127 s=16]",
    "chain_take_g2[gossip b=1024 c=1 e=1024]",
    # a sparse drain's aggregation: the smaller side at half the committee
    "chain_agg_corrected[gossip b=1024 w=256]",
    # one subnet flush: 4,096 one-bit votes, pubkeys gathered by index
    "chain_single_gather[subnet b=4096]",
    "chain_ladder_g1[subnet b=4096]",
])
def test_program_compiles_for_v5e(one_chip, programs, name):
    fn, shapes, static = programs[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in shapes]
    fn = getattr(fn, "jitted", fn)
    compiled = fn.lower(*args, **static).compile()  # raises the chip compiler's refusal
    mem = compiled.memory_analysis()
    footprint = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
    assert 0 < footprint < 16 << 30  # one v5e chip's HBM
    # plain jnp (a tree of hashes; an index gather); the rest are kernels
    if not name.startswith(("merkle_tree_jnp", "chain_single_gather", "chain_take_g")):
        assert "tpu_custom_call" in compiled.as_text()
