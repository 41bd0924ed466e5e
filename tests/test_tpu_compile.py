"""Compile the data-plane kernels for a TPU v5e chip at real widths.

No chip is attached: the topology is *described*, and each Pallas kernel
of the put/get/repair path is lowered and compiled for one of its chips
with ``interpret=False`` -- the step that interpret-mode tests cannot
cover (unsupported gathers, unaligned slices, block shapes, VMEM use).
Nothing runs, so this says nothing about results or speed.

The topology is described only inside the module fixture (never at
import or collection): only one process may load the TPU compiler's
library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf256
from repro.core.rs_code import RSCode, decode_matrix, generator_matrix
from repro.kernels import gear_cdc, gf_matmul, ops, sha1
from repro.kernels.launches import TRACES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        traces = TRACES.snapshot()
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()
            for fam in ("gf", "sha1", "gear", "fused"):
                setattr(TRACES, fam, getattr(traces, fam))


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _gbits(M):
    return gf256.gf_matrix_to_bits(np.asarray(M, np.uint8)).shape


def _sha1_blocks(max_len: int) -> int:
    return (max_len + 9 + 63) // 64


# (kernel, argument shapes/dtypes, static kwargs) at the widths the served
# path launches: a 64 MiB put-window gear stream, both preset codes, the
# SHA-1 caps of the staged (8 KiB / 16 KiB chunks) and fused paths
def _case(name):
    u8, u32, i32, f32 = jnp.uint8, jnp.uint32, jnp.int32, jnp.float32
    rt, ar = RSCode(10, 5), RSCode(14, 10)
    lp = 2048  # largest piece-length bucket of both presets
    cases = {
        "gear_fire_64MiB": (
            gear_cdc._gear_fire_padded,
            [((64 << 20,), u8), ((2, gear_cdc.LANES), i32), ((1,), u32)],
            {}),
        "gf_encode_10_5": (
            gf_matmul._gf_matmul_padded,
            [(_gbits(generator_matrix(10, 5)), f32), ((1024, 5, 1024), u8)],
            {}),
        "gf_encode_14_10": (
            gf_matmul._gf_matmul_padded,
            [(_gbits(generator_matrix(14, 10)), f32), ((256, 10, lp), u8)],
            {}),
        "gf_decode_5_5": (
            gf_matmul._gf_matmul_padded,
            [(_gbits(decode_matrix(10, 5, (1, 3, 5, 7, 9))), f32),
             ((1024, 5, 1024), u8)],
            {}),
        "sha1_8KiB_cap": (
            sha1._sha1_padded,
            [((512, _sha1_blocks(8192), 16), u32), ((512, 1), i32)],
            {"tile": sha1.TILE_B}),
        "sha1_archival_cap": (
            sha1._sha1_padded,
            [((512, _sha1_blocks(16384), 16), u32), ((512, 1), i32)],
            {"tile": sha1.TILE_B}),
        # a 64 MiB window's bucket holds thousands of chunks: these
        # batches ran out of VMEM with messages on sublanes
        "fused_realtime": (
            ops._fused_ingest_pallas,
            [(_gbits(generator_matrix(10, 5)), f32),
             ((4096, _sha1_blocks(rt.k * 1024), 16), u32), ((4096,), i32),
             ((4096, rt.k, 1024), u8)],
            {}),
        "fused_archival": (
            ops._fused_ingest_pallas,
            [(_gbits(generator_matrix(14, 10)), f32),
             ((1024, _sha1_blocks(ar.k * lp), 16), u32), ((1024,), i32),
             ((1024, ar.k, lp), u8)],
            {}),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "gear_fire_64MiB", "gf_encode_10_5", "gf_encode_14_10",
    "gf_decode_5_5", "sha1_8KiB_cap", "sha1_archival_cap",
    "fused_realtime", "fused_archival"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes, static = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel


# the instruction each kernel shows in a device trace, whatever the name
# of the jitted wrapper around it (the benchmark's rooflines match these)
TRACE_NAMES = {"gear_fire_64MiB": ["_gear_fire_padded"],
               "gf_encode_10_5": ["_gf_matmul_padded"],
               "sha1_8KiB_cap": ["_sha1_padded"],
               "fused_realtime": ["_sha1_padded", "_gf_matmul_padded"]}


@pytest.mark.parametrize("name", sorted(TRACE_NAMES))
def test_kernel_instruction_is_named_by_its_pallas_call(name, one_chip):
    fn, shapes, static = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    calls = [line.split(" = ", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert sorted(calls) == sorted(TRACE_NAMES[name])
