"""End-to-end system test: the paper's verification loop (Fig. 8) through
the FULL production path — depuncture -> framing -> unified Pallas kernel
(interpret) -> stitch."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FrameSpec, STD_K7, encode
from repro.core.pipeline import DecoderConfig, make_decoder
from repro.core.puncture import puncture
from repro.channel.sim import bpsk, awgn, ber


def test_sdr_receiver_end_to_end_kernel_path(rng):
    n = 20000
    bits = jnp.asarray(rng.integers(0, 2, n))
    coded = encode(bits, STD_K7)
    tx = bpsk(puncture(coded, "1/2"))
    rx = awgn(jax.random.PRNGKey(0), tx, 3.0)
    cfg = DecoderConfig(
        spec=FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45),
        backend="kernel", interpret=True)
    dec = make_decoder(cfg)
    out = dec(rx, n)
    b = float(ber(out, bits))
    assert b < 2e-3, b        # ~theory at 3 dB with parallel traceback

    # the pure-JAX reference backend decodes identically
    cfg2 = DecoderConfig(
        spec=FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45),
        backend="reference")
    out2 = make_decoder(cfg2)(rx, n)
    assert np.array_equal(np.asarray(out), np.asarray(out2))

