"""Quickstart: encode -> AWGN channel -> unified-kernel Viterbi decode.

PYTHONPATH=src python examples/quickstart.py

DecoderConfig knobs beyond the defaults shown here:
  * pack_survivors / radix — bit-packed survivors and two trellis stages
    per scan step (the defaults); every combination is bit-identical.
  * frames_per_tile='auto' (default) sizes the kernel's frame tile from
    its VMEM footprint (kernels/autotune.plan_tiles).

For unbounded inputs, use the STREAMING front-end instead of one shot:

    from repro.core import make_stream_decoder
    sdec = make_stream_decoder(cfg)           # chunk size from plan_decode
    bits_so_far = sdec.push(llr_chunk)        # async, double-buffered
    ...                                       # push as samples arrive
    tail = sdec.flush()                       # zero-padded tail + drain

Chunked output is bit-identical to the single-shot decode; pass ``mesh=``
(distributed.stream.frame_mesh()) to tile each chunk's frames across
devices.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FrameSpec, STD_K7, encode
from repro.core.pipeline import DecoderConfig, make_decoder
from repro.channel.sim import awgn, ber, bpsk

n = 20_000
rng = np.random.default_rng(0)
bits = jnp.asarray(rng.integers(0, 2, n))

# transmitter: standard (2,1,7) code, generators 171/133 (paper Fig. 1)
tx = bpsk(encode(bits, STD_K7).reshape(-1))

# channel: 3 dB Eb/N0
rx = awgn(jax.random.PRNGKey(1), tx, 3.0)

# receiver: the paper's unified kernel (forward + parallel traceback in one
# Pallas kernel, survivor paths in VMEM only) — compiled on a TPU,
# interpreted elsewhere (DecoderConfig's platform defaults)
cfg = DecoderConfig(spec=FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45),
                    backend="kernel")
decode = make_decoder(cfg)
out = decode(rx.reshape(n, 2), n)

print(f"decoded {n} bits, BER = {float(ber(out, bits)):.2e} @ 3 dB "
      f"(theory ~1e-3)")
