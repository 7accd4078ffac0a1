"""Ahead-of-time compiles of the OLTP kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology, at the shapes the served path and
``chip_smoke.py`` use.  A kernel the chip's compiler refuses (unaligned
block, too much fast memory, a program over the chip's 16 GB) fails here
without chip time.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bucketing import bucket

V5E_HBM_BYTES = 16 * 10**9

# name -> (jitted entry point, argument shapes, static kwargs, is a Pallas
# kernel).  Shapes: served cuts are <= 256 conflict-free txns of one
# write lane, or of a read and a write lane (YCSB-F's read-modify-writes);
# bulk rounds are 8,192 txns of one access against a 10M-row table; a
# recovery tile holds ~1-2K write lanes (1.25 MiB sealed segments).
CASES = {
    "seg_reduce_min_8": (ops.occ_seg_reduce, [(8,), (8,)],
                         dict(n_slots=8, op="min", interpret=False), True),
    "seg_reduce_max_8": (ops.occ_seg_reduce, [(8,), (8,)],
                         dict(n_slots=8, op="max", interpret=False), True),
    "seg_reduce_min_256": (ops.occ_seg_reduce, [(256,), (256,)],
                           dict(n_slots=256, op="min", interpret=False), True),
    "seg_reduce_max_256": (ops.occ_seg_reduce, [(256,), (256,)],
                           dict(n_slots=256, op="max", interpret=False), True),
    "seg_reduce_max_512_of_256": (ops.occ_seg_reduce, [(512,), (512,)],
                                  dict(n_slots=256, op="max",
                                       interpret=False), True),
    "ssn_scatter_max": (ops.ssn_scatter_max,
                        [(4096,), (4096,), (2048,), (2048,), (2048,)],
                        dict(interpret=False), True),
    "fused_validate_sequence": (ops.fused_validate_sequence,
                                [(6, 8192), (8192,)],
                                dict(n_txn=8192, k=1, cap=bucket(10_000_000)),
                                False),
    "fused_replay_scan": (ops.fused_replay_scan, [(3, 2048)],
                          dict(n_slots=4096), False),
    "fused_replay_apply": (ops.fused_replay_apply, [(2, 4096), (3, 2048)],
                           {}, False),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes, static, is_pallas = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == is_pallas
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
