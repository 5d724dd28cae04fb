"""Compile the device decode path's Pallas kernels for a TPU v5e chip.

Nothing runs: each kernel is lowered and compiled at real widths for a
chip that is described, not attached, so a kernel the TPU compiler would
refuse (a block off the tiling, an unsupported primitive) fails here with
no chip.  Widths: a whole 65,536-value morsel, a 512-value morsel (shorter
than one lane block), a default 8,192-value page and a default
131,072-row group (the two-phase reader's range mask).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (bitunpack, bss_decode, delta_decode, filter_range,
                           page_minmax)
from repro.kernels.segmented import (seg_bitunpack, seg_delta_decode,
                                     seg_dict_decode)

MORSEL = 65_536
PAGE = 8_192
ROW_GROUP = 131_072


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _seg_args(sharding, n):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    # words, w0, sh, mask as plan_segments stages them for n values
    return [s((2 * n,), jnp.uint32), s((n,), jnp.int32),
            s((n,), jnp.uint32), s((n,), jnp.uint32)], s


@pytest.mark.parametrize("n", [MORSEL, 512])
def test_seg_bitunpack(one_chip, n):
    head, s = _seg_args(one_chip, n)
    _compile(seg_bitunpack, *head, s((n,), jnp.int32))


@pytest.mark.parametrize("n", [MORSEL, 512])
def test_seg_dict_decode(one_chip, n):
    head, s = _seg_args(one_chip, n)
    _compile(seg_dict_decode, *head, s((460,), jnp.int32),
             s((n,), jnp.int32))


@pytest.mark.parametrize("n", [MORSEL, 512])
def test_seg_delta_decode(one_chip, n):
    head, s = _seg_args(one_chip, n)
    _compile(seg_delta_decode, *head, s((n,), jnp.int32), s((8,), jnp.int32),
             s((n,), jnp.int32), s((8,), jnp.int32), s((1,), jnp.int32))


@pytest.mark.parametrize("k", [3, 8, 17])
def test_bitunpack(one_chip, k):
    words = jax.ShapeDtypeStruct((MORSEL * k // 32,), jnp.uint32,
                                 sharding=one_chip)
    _compile(lambda w: bitunpack(w, MORSEL, k), words)


def test_bss_decode(one_chip):
    # a morsel's float32 pages as four byte planes side by side
    _compile(bss_decode,
             jax.ShapeDtypeStruct((4, MORSEL), jnp.uint8, sharding=one_chip))


def test_delta_decode(one_chip):
    _compile(delta_decode,
             jax.ShapeDtypeStruct((PAGE,), jnp.uint32, sharding=one_chip),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.uint32])
def test_filter_range(one_chip, dtype):
    _compile(lambda x: filter_range(x, 3, 10),
             jax.ShapeDtypeStruct((PAGE,), dtype, sharding=one_chip))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_filter_range_row_group(one_chip, dtype):
    _compile(lambda x: filter_range(x, 3, 10),
             jax.ShapeDtypeStruct((ROW_GROUP,), dtype, sharding=one_chip))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.uint32])
def test_page_minmax(one_chip, dtype):
    _compile(lambda x: page_minmax(x, 4096),
             jax.ShapeDtypeStruct((MORSEL,), dtype, sharding=one_chip))
