"""Unit tests for kernels/packing.py — the survivor bit-packing the kernel
does each stage (``pack_rows``: states on sublanes, frames on lanes), and
the word select its traceback reads it back with."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.packing import BITS, pack_rows, packed_width


def _packed(sel) -> np.ndarray:
    """(n, N) selectors -> (packed_width(n), N) int32 words."""
    return np.concatenate([np.asarray(w) for w in pack_rows(jnp.asarray(sel))])


def _unpack(words: np.ndarray, n: int) -> np.ndarray:
    """Numpy inverse: bit s % 32 of word s // 32, for s < n."""
    s = np.arange(n)
    return (words[s // BITS] >> (s % BITS)[:, None]) & 1


def _select(words: np.ndarray, states: np.ndarray) -> np.ndarray:
    """The kernel traceback's read of state ``states[j]`` in lane j: the
    word ``states >> 5`` picked by compares, then bit ``states & 31``."""
    word = words[0]
    for w in range(1, words.shape[0]):
        word = np.where((states >> 5) == w, words[w], word)
    return (word >> (states & 31)) & 1


@pytest.mark.parametrize("n", [1, 8, 31, 32, 33, 64, 100])
def test_pack_unpack_roundtrip_sublane(rng, n):
    """pack_rows packs the state axis (sublanes) and leaves the trailing
    frames-on-lanes axis alone — for any n, incl. n % 32 != 0."""
    sel = rng.integers(0, 2, size=(n, 6))
    words = pack_rows(jnp.asarray(sel))
    assert len(words) == packed_width(n)
    assert all(w.shape == (1, 6) and w.dtype == jnp.int32 for w in words)
    assert np.array_equal(_unpack(_packed(sel), n), sel)


def test_packed_width():
    assert [packed_width(n) for n in (1, 31, 32, 33, 64, 65)] == \
        [1, 1, 1, 2, 2, 3]


def test_layout_matches_numpy_bitorder(rng):
    """State s lands at bit s%32 of word s//32 (contiguous little-endian)."""
    sel = rng.integers(0, 2, size=(64, 1))
    packed = _packed(sel)[:, 0]
    want = np.packbits(sel[:, 0].astype(np.uint8), bitorder="little")
    assert np.array_equal(packed.view(np.uint8), want)


def test_sign_bit_roundtrip():
    """Bit 31 uses the int32 sign bit; wraparound must keep it exact."""
    sel = np.zeros((32, 1), np.int64)
    sel[31] = 1
    packed = _packed(sel)
    assert packed[0, 0] == np.int32(-2**31)
    assert np.array_equal(_unpack(packed, 32), sel)


@pytest.mark.parametrize("n", [8, 33, 64, 100])
def test_extract_bit_matches_indexing_sublane(rng, n):
    """The traceback's word select reads back each lane's state bit."""
    sel = rng.integers(0, 2, size=(n, 9))
    states = rng.integers(0, n, size=(9,)).astype(np.int32)
    got = _select(_packed(sel), states)
    assert np.array_equal(got, sel[states, np.arange(9)])
