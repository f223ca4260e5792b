"""Framed (tiled) parallel Viterbi decoding (paper §III Fig. 2, §IV).

The n-stage stream is cut into F = ceil(n/f) frames. Frame m decodes output
stages [m*f, (m+1)*f) but *processes* stages [m*f - v1, m*f + f + v2): the
left overlap v1 warms up the path metrics, the right overlap v2 lets the
survivor path converge before the kept region (paper Fig. 2b). Frames are
embarrassingly parallel: vmap here, grid axis in the Pallas kernel, and the
sharded axis of the frame mesh (distributed/stream.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .decoder import viterbi_forward
from .traceback import parallel_traceback, serial_traceback
from .trellis import Trellis

__all__ = ["FrameSpec", "frame_llr", "decode_frame", "framed_decode",
           "reframe_blocks", "merge_blocks", "flatten_bits"]


@dataclasses.dataclass(frozen=True)
class FrameSpec:
    """Tiling parameters (paper notation)."""
    f: int = 256          # kept stages per frame
    v1: int = 20          # left overlap (warm-up)
    v2: int = 20          # right overlap (traceback convergence)
    f0: int = 0           # subframe length for parallel traceback (0 = serial)
    v2s: int = 0          # subframe overlap (parallel traceback)
    start: str = "boundary"   # parallel-traceback start-state strategy

    @property
    def frame_len(self) -> int:       # L = v1 + f + v2
        return self.v1 + self.f + self.v2

    @property
    def parallel_tb(self) -> bool:
        return self.f0 > 0

    def num_frames(self, n: int) -> int:
        return -(-n // self.f)

    def validate(self):
        if self.parallel_tb:
            if self.f % self.f0 != 0:
                raise ValueError(
                    f"f={self.f} is not a multiple of f0={self.f0}; the "
                    f"parallel traceback needs f % f0 == 0 (paper §IV-E)")
            if self.v2s > self.v2:
                raise ValueError(
                    f"v2s={self.v2s} exceeds v2={self.v2}; the subframe "
                    f"convergence overlap must fit in the frame overlap")

    def blocked(self, block_frames: int, overlap: int) -> "FrameSpec":
        """The per-block FrameSpec of the intra-frame block-parallel
        decode: each frame's f kept stages split into ``block_frames``
        blocks of ``f / block_frames`` stages, every block carrying an
        ``overlap``-stage training region on the left (metric warm-up)
        and truncation region on the right (traceback convergence) — the
        standard block-based truncated-traceback construction (arXiv
        1608.00066). Blocks are just shorter frames, so the derived spec
        is decoded by the unchanged frame machinery; a parallel-traceback
        geometry carries over (f0 must divide the block, v2s must fit the
        block overlap)."""
        B, ov = int(block_frames), int(overlap)
        if B < 1:
            raise ValueError(f"block_frames must be >= 1, got {block_frames}")
        if ov < 0:
            raise ValueError(f"overlap must be >= 0, got {overlap}")
        if self.f % B != 0:
            raise ValueError(
                f"f={self.f} is not a multiple of block_frames={B}; "
                f"intra-frame blocking needs f % block_frames == 0")
        fb = self.f // B
        if self.parallel_tb:
            if fb % self.f0 != 0:
                raise ValueError(
                    f"block length f/block_frames={fb} is not a multiple "
                    f"of f0={self.f0}; shrink f0 or use fewer blocks")
            if self.v2s > ov:
                raise ValueError(
                    f"v2s={self.v2s} exceeds the block overlap={ov}; the "
                    f"subframe convergence region must fit in it")
        sub = FrameSpec(f=fb, v1=ov, v2=ov,
                        f0=self.f0 if self.parallel_tb else 0,
                        v2s=self.v2s if self.parallel_tb else 0,
                        start=self.start)
        sub.validate()
        return sub


def frame_llr(llr: jax.Array, spec: FrameSpec) -> jax.Array:
    """(n, beta) -> (F, L, beta) overlapping frames, zero-padded at edges.

    Zero LLR is neutral to the metrics — identical to how de-puncturing
    treats erased symbols (paper §IV-E), so edge padding is BER-safe.
    """
    n, beta = llr.shape
    F = spec.num_frames(n)
    pad_r = F * spec.f + spec.v2 - n
    padded = jnp.pad(llr, ((spec.v1, pad_r), (0, 0)))
    starts = jnp.arange(F) * spec.f
    idx = starts[:, None] + jnp.arange(spec.frame_len)[None, :]
    return padded[idx]                                # (F, L, beta)


def decode_frame(llr_frame: jax.Array, trellis: Trellis,
                 spec: FrameSpec, renorm_every: int = 1) -> jax.Array:
    """Decode one (L, beta) frame -> (f,) bits. Pure-JAX reference path.

    ``renorm_every`` is the path-metric renormalization period (see
    viterbi_forward; 1 = the historical per-stage normalization)."""
    sel, sigma, amax = viterbi_forward(                     # uniform sigma0
        llr_frame, trellis, renorm_every=renorm_every)
    if spec.parallel_tb:
        return parallel_traceback(sel, amax, trellis, spec.v1, spec.f,
                                  spec.f0, spec.v2s, spec.start)
    start = jnp.argmax(sigma).astype(jnp.int32)
    return serial_traceback(sel, trellis, start, spec.v1, spec.f)


def reframe_blocks(frames: jax.Array, spec: FrameSpec, block_frames: int,
                   overlap: int) -> jax.Array:
    """(F, L, beta) frames -> (F*B, fb + 2*overlap, beta) block windows.

    Block b of a frame covers frame stages
    ``[v1 + b*fb - overlap, v1 + (b+1)*fb + overlap)`` — its fb kept
    stages plus the training/truncation overlaps — gathered exactly like
    ``frame_llr`` gathers frames from the stream, with zero padding where
    a window reaches past the frame (zero LLR is metric-neutral, the same
    edge treatment as frame_llr / depuncturing). When
    ``overlap <= min(v1, v2)`` every window lies inside the frame and the
    blocked decode is bit-identical to re-framing the stream with
    ``spec.blocked(block_frames, overlap)``."""
    F = frames.shape[0]
    B, ov = int(block_frames), int(overlap)
    fb = spec.f // B
    pad_l = max(0, ov - spec.v1)
    pad_r = max(0, ov - spec.v2)
    padded = jnp.pad(frames, ((0, 0), (pad_l, pad_r), (0, 0)))
    starts = pad_l + spec.v1 - ov + jnp.arange(B) * fb
    idx = starts[:, None] + jnp.arange(fb + 2 * ov)[None, :]
    blocks = padded[:, idx]                           # (F, B, Lb, beta)
    return blocks.reshape(F * B, fb + 2 * ov, frames.shape[2])


def merge_blocks(bits: jax.Array, block_frames: int) -> jax.Array:
    """(F*B, fb) per-block kept bits -> (F, f) frame bits. The trailing
    overlap was already truncated by the per-block decode (a block keeps
    only its fb body stages), so the merge is a pure reshape, behind the
    same barrier as ``flatten_bits``."""
    FB, fb = bits.shape
    return jax.lax.optimization_barrier(bits).reshape(
        FB // int(block_frames), int(block_frames) * fb)


def flatten_bits(bits: jax.Array) -> jax.Array:
    """(F, f) decoded frames -> (F*f,) stream order, for use inside a jit.

    The (F, f) bits are materialized before the reshape: fused into the
    reference decode's traceback, the TPU compiler flattens 8192 and
    16384 f=256 frames wrongly, every frame from bit 16 on (PERF.md;
    ``scripts/chip_witness.py`` reproduces it)."""
    return jax.lax.optimization_barrier(bits).reshape(-1)


@partial(jax.jit, static_argnums=(1, 2, 3))
def framed_decode(llr: jax.Array, trellis: Trellis, spec: FrameSpec,
                  n_out: int | None = None) -> jax.Array:
    """Full framed decode: (n, beta) llr -> (n,) bits (vmap over frames)."""
    spec.validate()
    n = llr.shape[0] if n_out is None else n_out
    frames = frame_llr(llr, spec)                     # (F, L, beta)
    bits = jax.vmap(lambda fr: decode_frame(fr, trellis, spec))(frames)
    return flatten_bits(bits)[:n]
