"""Pallas kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py
oracles and vs the numpy codecs (interpret=True executes kernel bodies on CPU).
"""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import encodings as enc
from repro.kernels import ops, ref
from repro.kernels.bitunpack import bitunpack
from repro.kernels.bss_decode import bss_decode
from repro.kernels.delta_decode import delta_decode
from repro.kernels.dict_decode import dict_decode
from repro.kernels.filter_kernel import filter_range
from repro.kernels.stats_kernel import page_minmax

RNG = np.random.default_rng(42)


def _packed_words(vals, k):
    buf = enc.pack_bits(vals.astype(np.uint64), k)
    pad = (-len(buf)) % 4
    return jnp.asarray(np.frombuffer(buf + b"\0" * pad, np.uint32))


class TestBitunpack:
    @pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 11, 13, 16, 17, 24, 31, 32])
    @pytest.mark.parametrize("n", [1, 7, 1024, 1025, 5000])
    def test_sweep_vs_oracle(self, k, n):
        hi = 2**k if k < 32 else 2**31
        vals = RNG.integers(0, hi, n).astype(np.uint64)
        words = _packed_words(vals, k)
        out = bitunpack(words, n, k, interpret=True)
        oracle = ref.bitunpack(words, n, k)
        np.testing.assert_array_equal(
            np.asarray(out).astype(np.uint32), vals.astype(np.uint32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))

    def test_k0(self):
        out = bitunpack(jnp.zeros(0, jnp.uint32), 5, 0, interpret=True)
        assert out.tolist() == [0] * 5


class TestDictDecode:
    @pytest.mark.parametrize("d", [1, 2, 37, 1000])
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_sweep(self, d, dtype):
        dictionary = (RNG.standard_normal(d) * 100).astype(dtype)
        idx = RNG.integers(0, d, 777).astype(np.int32)
        out = dict_decode(jnp.asarray(idx), jnp.asarray(dictionary),
                          interpret=True)
        oracle = ref.dict_decode(jnp.asarray(idx), jnp.asarray(dictionary))
        np.testing.assert_allclose(np.asarray(out), dictionary[idx], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=1e-6)

    def test_large_dict_falls_back_to_gather(self):
        dictionary = np.arange(10_000, dtype=np.int32)
        idx = RNG.integers(0, 10_000, 100).astype(np.int32)
        out = dict_decode(jnp.asarray(idx), jnp.asarray(dictionary),
                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out), dictionary[idx])


class TestDeltaDecode:
    @pytest.mark.parametrize("n", [1, 2, 100, 2048, 2049, 9999])
    def test_sweep_vs_numpy_codec(self, n):
        arr = np.cumsum(RNG.integers(-100, 101, n)).astype(np.int64)
        arr = np.clip(arr, -2**30, 2**30)  # int32 range on device
        chosen, meta, payload = enc.encode(arr, "delta")
        out = ops.decode_on_device(chosen, meta, payload, n, np.int32,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(out), arr.astype(np.int32))

    def test_carry_across_blocks(self):
        # block boundary at 2048: the SMEM carry must thread through
        n = 4096 + 7
        arr = np.arange(n, dtype=np.int64) * 3 + 11
        chosen, meta, payload = enc.encode(arr, "delta")
        out = ops.decode_on_device(chosen, meta, payload, n, np.int32,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(out), arr.astype(np.int32))

    def test_vs_oracle(self):
        zz = jnp.asarray(RNG.integers(0, 50, 3000).astype(np.uint32))
        first = jnp.int32(-17)
        np.testing.assert_array_equal(
            np.asarray(delta_decode(zz, first, interpret=True)),
            np.asarray(ref.delta_decode(zz, first)))


class TestBssDecode:
    @pytest.mark.parametrize("n", [1, 100, 2048, 4097])
    def test_sweep(self, n):
        arr = RNG.standard_normal(n).astype(np.float32)
        _, meta, payload = enc.encode(arr, "bss")
        planes = jnp.asarray(np.frombuffer(payload, np.uint8).reshape(4, n))
        out = bss_decode(planes, interpret=True)
        oracle = ref.bss_decode(planes)
        np.testing.assert_array_equal(np.asarray(out), arr)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))

    def test_specials(self):
        arr = np.array([0.0, -0.0, np.inf, -np.inf, 1e-38, 3.4e38], np.float32)
        _, meta, payload = enc.encode(arr, "bss")
        planes = jnp.asarray(np.frombuffer(payload, np.uint8).reshape(4, len(arr)))
        out = bss_decode(planes, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), arr)


class TestFilterKernel:
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    @pytest.mark.parametrize("n", [5, 2048, 6000])
    def test_sweep(self, dtype, n):
        x = (RNG.standard_normal(n) * 100).astype(dtype)
        mask, counts = filter_range(jnp.asarray(x), -50, 50,
                                    interpret=True)
        oracle = np.asarray(ref.filter_range(jnp.asarray(x), dtype(-50), dtype(50)))
        np.testing.assert_array_equal(np.asarray(mask), oracle)
        assert int(counts.sum()) == int(oracle.sum())

    def test_empty_range(self):
        x = jnp.arange(100, dtype=jnp.int32)
        mask, counts = filter_range(x, 1000, 2000, interpret=True)
        assert int(counts.sum()) == 0 and not bool(mask.any())


class TestStatsKernel:
    @pytest.mark.parametrize("page", [128, 1024])
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_sweep(self, page, dtype):
        n = page * 7 + 13
        x = (RNG.standard_normal(n) * 1000).astype(dtype)
        mins, maxs = page_minmax(jnp.asarray(x), page, interpret=True)
        # compare on the full pages; ragged tail is padded with x[-1]
        xr = np.concatenate([x, np.full(page * 8 - n, x[-1], dtype)]).reshape(8, page)
        np.testing.assert_array_equal(np.asarray(mins), xr.min(1))
        np.testing.assert_array_equal(np.asarray(maxs), xr.max(1))

    def test_vs_oracle_exact_pages(self):
        x = jnp.asarray(RNG.standard_normal(4096).astype(np.float32))
        mins, maxs = page_minmax(x, 512, interpret=True)
        omin, omax = ref.page_minmax(x, 512)
        np.testing.assert_array_equal(np.asarray(mins), np.asarray(omin))
        np.testing.assert_array_equal(np.asarray(maxs), np.asarray(omax))


@given(st.integers(1, 31), st.integers(1, 400))
@settings(max_examples=30, deadline=None)
def test_property_bitunpack_any_k_n(k, n):
    vals = RNG.integers(0, 2**k, n).astype(np.uint64)
    out = bitunpack(_packed_words(vals, k), n, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.uint64), vals)


@given(st.lists(st.integers(-2**20, 2**20), min_size=1, max_size=300))
@settings(max_examples=30, deadline=None)
def test_property_delta_device_matches_host(xs):
    arr = np.array(xs, np.int64)
    chosen, meta, payload = enc.encode(arr, "delta")
    host = enc.decode(chosen, meta, payload, len(arr), np.int64)
    dev = ops.decode_on_device(chosen, meta, payload, len(arr), np.int32,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(dev), host.astype(np.int32))


def test_end_to_end_page_decode_matches_host():
    """Write a TPQ page, decode the same buffers on 'device', compare."""
    for encoding in ("bitpack", "dict", "delta", "bss"):
        if encoding == "bss":
            arr = RNG.standard_normal(3000).astype(np.float32)
        else:
            arr = np.sort(RNG.integers(0, 2**20, 3000)).astype(np.int64)
        chosen, meta, payload = enc.encode(arr, encoding)
        host = enc.decode(chosen, meta, payload, len(arr), arr.dtype)
        dt = np.float32 if encoding == "bss" else (
            np.int64 if encoding == "dict" else np.int32)
        dev = np.asarray(ops.decode_on_device(
            chosen, meta, payload, len(arr), dt, interpret=True))
        np.testing.assert_array_equal(dev.astype(arr.dtype), host)
