import os
import sys

# tests must see exactly ONE device (the dry-run sets its own flags in a
# separate process); never inherit a stray device-count flag.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def std_trellis():
    from repro.core import STD_K7
    return STD_K7


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


def noisy_llr(bits, trellis, snr_db, rng):
    """Encode bits, BPSK, add AWGN -> (n, beta) llr numpy."""
    import jax.numpy as jnp
    from repro.core import encode
    coded = np.asarray(encode(jnp.asarray(bits), trellis))
    tx = 1.0 - 2.0 * coded.astype(np.float32)
    sigma = 10.0 ** (-snr_db / 20.0)
    return tx + sigma * rng.standard_normal(tx.shape).astype(np.float32)


def kernel_matches_reference(trellis, spec, radix, pack, seed, frames=16,
                             snr_db=2.0):
    """Decode ``frames`` noisy frames of ``spec`` with the unified kernel,
    interpreted (the program Mosaic compiles on a TPU), and assert its
    bits equal the pure-jnp reference's."""
    import jax.numpy as jnp
    from repro.core.framed import frame_llr
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, frames * spec.f)
    batch = frame_llr(jnp.asarray(noisy_llr(bits, trellis, snr_db, rng)),
                      spec)
    assert batch.shape[0] == frames
    want = np.asarray(ref.unified_decode_frames_ref(batch, trellis, spec))
    got = np.asarray(ops.viterbi_decode_frames(
        batch, trellis, spec, pack_survivors=pack, radix=radix,
        interpret=True))
    assert np.array_equal(got, want)
