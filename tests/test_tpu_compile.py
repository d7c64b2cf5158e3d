"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel (or the jitted device codec) at
the width a real run uses and asks the installed TPU compiler to build
it, which refuses layouts, tilings and VMEM use that interpret mode on
the CPU accepts.  A 2^20-amplitude block is an (8192, 128) plane; a
group of 2^22 amplitudes is what the planner picks for n = 28.

The topology is described inside a module-scoped fixture (never at
import), and these tests stay in this one file: only one process may
load the TPU compiler's library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.compression import device_codec as dc
from repro.compression.pwrel import log_step
from repro.kernels import gate_apply as ga
from repro.kernels import pack as pk
from repro.kernels import quantize as qz

BLOCK = 1 << 20            # amplitudes per SV block (local_bits = 20)
ROWS = BLOCK // 128        # rows of one f32 plane
GROUP = 1 << 22            # amplitudes per SV group (nv = 22)
STEP = log_step(1e-3)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the Pallas kernel must be
    in the program as a Mosaic custom call, not interpreted."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_quantize_tiles(spec):
    _compile(lambda x, l: qz.quantize_tiles(x, l, STEP),
             spec((ROWS, 128), jnp.float32), spec((1, 1), jnp.float32))


def test_dequantize_tiles(spec):
    _compile(lambda c, p, l: qz.dequantize_tiles(c, p, l, STEP),
             spec((ROWS, 128), jnp.int32), spec((ROWS, 4), jnp.int32),
             spec((1, 1), jnp.float32))


def test_pack_codes_tiles(spec):
    _compile(pk.pack_codes_tiles, spec((ROWS, 128), jnp.int32))


def test_unpack_codes_tiles(spec):
    _compile(pk.unpack_codes_tiles, spec((ROWS, 64), jnp.int32))


def test_pack_bitmap_tiles(spec):
    _compile(pk.pack_bitmap_tiles, spec((ROWS, 128), jnp.int32))


def test_unpack_bitmap_tiles(spec):
    _compile(pk.unpack_bitmap_tiles, spec((ROWS, 4), jnp.int32))


@pytest.mark.parametrize("K", [32, 128])
def test_gemm_planes(spec, K):
    a = spec((GROUP // K, K), jnp.float32)
    b = spec((K, K), jnp.float32)
    _compile(ga.gemm_planes, a, a, b, b)


def test_gemm_planes_mid(spec):
    K, inner = 32, 1 << 10
    a = spec((GROUP // (K * inner), K, inner), jnp.float32)
    b = spec((K, K), jnp.float32)
    _compile(ga.gemm_planes_mid, a, a, b, b)


def test_diag_apply(spec):
    K = 128
    a = spec((GROUP // K, K), jnp.float32)
    d = spec((K,), jnp.float32)
    _compile(ga.diag_apply, a, a, d, d)


def test_device_encode_block(spec):
    compiled = _compile(
        lambda p: dc._encode_group_jit(p, n_blocks=1, step=STEP),
        spec((2, BLOCK), jnp.float32))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20


def test_device_decode_block(spec):
    compiled = _compile(
        lambda c, s, l: dc._decode_blocks_jit(c, s, l, n=BLOCK, step=STEP),
        spec((2, BLOCK // 2), jnp.int32), spec((2, BLOCK // 32), jnp.int32),
        spec((2, 1, 1), jnp.float32))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
