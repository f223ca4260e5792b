"""Jitted public wrappers around the Pallas Viterbi kernels.

Handles frame-count padding to the tile size, selects unified vs split
(forward kernel + separate traceback) execution, resolves the
``frames_per_tile='auto'`` tile plan (kernels/autotune.py — budgeting the
kernel that will actually run), and exposes one call the rest of the
framework uses: ``viterbi_decode_frames``.

Defaults are the library's best-known configuration (bit-packed survivors,
radix-4, autotuned tiles — the same defaults as core.pipeline.DecoderConfig);
pass ``pack_survivors=False, radix=2, frames_per_tile=8`` explicitly to
reproduce the seed kernel behavior.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.framed import FrameSpec, merge_blocks, reframe_blocks
from ..core.pipeline import platform_defaults
from ..core.traceback import parallel_traceback_frames, serial_traceback_frames
from ..core.trellis import Trellis
from ..obs.tracer import get_tracer
from .autotune import plan_tiles
from .packing import Layout
from .viterbi_fwd import forward_frames
from .viterbi_unified import unified_decode_frames

__all__ = ["viterbi_decode_frames"]


def _pad_frames(frames: jax.Array, tile: int):
    F = frames.shape[0]
    Fp = -(-F // tile) * tile
    if Fp != F:
        frames = jnp.pad(frames, ((0, Fp - F), (0, 0), (0, 0)))
    return frames, F


@partial(jax.jit, static_argnames=("trellis", "spec", "unified",
                                   "frames_per_tile", "pack_survivors",
                                   "radix", "layout", "bm_dtype",
                                   "block_frames", "overlap", "interpret"))
def viterbi_decode_frames(frames: jax.Array, trellis: Trellis,
                          spec: FrameSpec, *, unified: bool = True,
                          frames_per_tile: int | str = "auto",
                          pack_survivors: bool = True, radix: int = 4,
                          layout: str | None = None,
                          bm_dtype: str = "float32",
                          block_frames: int = 1, overlap: int = 0,
                          interpret: bool | None = None) -> jax.Array:
    """(F, L, beta) LLR frames -> (F, f) decoded bits.

    unified=True  : the paper's single-kernel path (survivors in VMEM only).
    unified=False : prior-work baseline — forward kernel streams survivors
                    to HBM, traceback runs as a separate batched step
                    (interpret mode and the lane layout only).
    frames_per_tile: frames decoded per kernel grid step, or 'auto' to let
                    the VMEM-budget planner choose (autotune.plan_tiles,
                    budgeting whichever kernel/layout/dtype runs here).
    pack_survivors: bit-pack the survivor array 32x (VMEM scratch for the
                    unified kernel, the HBM stream for the split baseline).
    radix         : 2, or 4 to fuse two trellis stages per ACS/traceback
                    step.
    layout        : 'lane' (frames on sublanes, original orientation,
                    interpret mode only) or 'sublane' (frames on lanes,
                    the layout Mosaic compiles). None takes the
                    platform's: 'sublane' on a TPU, else 'lane'.
    bm_dtype      : 'float32' | 'bfloat16' branch-metric storage. All knob
                    combinations decode bit-identically except bf16, which
                    quantizes the metrics once (BER-neutral to ~1e-3).
    block_frames  : >1 engages intra-frame block-parallel decode
                    (kernels/block.py): each frame re-framed into
                    block_frames blocks of f/B + 2*overlap stages on the
                    frame axis, decoded by this same kernel under the
                    derived spec, merged by truncating each block's
                    overlap. The second knob besides bf16 that is not
                    bit-exact: a truncated-traceback approximation,
                    BER-gated to 1e-3 at overlap ~5*K, and exactly
                    bit-identical when overlap >= block.full_overlap().
    overlap       : per-block training/truncation region (stages); only
                    meaningful with block_frames > 1.
    interpret     : run the Pallas kernels in the interpreter. None takes
                    the platform's: compiled on a TPU, interpreted
                    elsewhere. A compiled request for a knob Mosaic cannot
                    run (lane layout, bf16 metrics, split kernel) raises
                    ValueError instead of falling back to the interpreter.
    """
    if interpret is None:
        interpret = platform_defaults()["interpret"]
    if layout is None:
        layout = platform_defaults()["layout"]
    spec.validate()
    # entry validation (trace-time, so invalid calls fail with a clear
    # message instead of a shape error deep inside a kernel)
    if frames.ndim != 3:
        raise ValueError(
            f"frames must be (F, L, beta), got {frames.ndim}-D "
            f"{frames.shape}")
    if frames.shape[1] != spec.frame_len:
        raise ValueError(
            f"frames.shape[1]={frames.shape[1]} != spec.frame_len="
            f"{spec.frame_len} (v1 + f + v2 overlap window)")
    if frames.shape[2] != trellis.beta:
        raise ValueError(
            f"frames.shape[2]={frames.shape[2]} != trellis.beta="
            f"{trellis.beta} coded bits per stage")
    if not jnp.issubdtype(frames.dtype, jnp.floating):
        raise ValueError(
            f"frames must be floating-point LLRs, got dtype "
            f"{frames.dtype}")
    F_in = frames.shape[0]
    if block_frames < 1:
        raise ValueError(f"block_frames must be >= 1, got {block_frames}")
    if block_frames > 1:
        # intra-frame block-parallel mode: re-frame (F, L) frames into
        # (F*B, f/B + 2*overlap) blocks on the same frame axis and decode
        # them below under the derived spec — the tile planner, padding,
        # kernels and traceback all see ordinary (short) frames
        sub = spec.blocked(block_frames, overlap)
        frames = reframe_blocks(frames, spec, block_frames, overlap)
        spec = sub
    lay = Layout(layout)
    if frames_per_tile == "auto":
        frames_per_tile = plan_tiles(
            trellis, spec, pack_survivors=pack_survivors, radix=radix,
            unified=unified, layout=lay, bm_dtype=bm_dtype,
            max_frames=frames.shape[0]).frames_per_tile
    # serial traceback == one subframe spanning the kept region (DESIGN §2)
    f0 = spec.f0 if spec.parallel_tb else spec.f
    v2s = spec.v2s if spec.parallel_tb else spec.v2
    start = spec.start if spec.parallel_tb else "boundary"

    # This function body runs at jit *trace* time only — so this event
    # marks each real XLA compile of a decode program (re-launches of the
    # cached executable never reach here). One glance at a trace file
    # answers "how many distinct kernels did this run compile, and with
    # which knobs?".
    trace = get_tracer()
    trace.event("kernel_trace", kernel="unified" if unified else "split",
                states=trellis.num_states, frames=int(frames.shape[0]),
                frames_per_tile=int(frames_per_tile), layout=lay.value,
                bm_dtype=str(bm_dtype), radix=int(radix),
                pack_survivors=bool(pack_survivors),
                block_frames=int(block_frames), overlap=int(overlap),
                interpret=bool(interpret))
    trace.count("kernel_traces")

    padded, F = _pad_frames(frames, frames_per_tile)
    if unified:
        bits = unified_decode_frames(
            padded, trellis=trellis, v1=spec.v1, f=spec.f, v2=spec.v2,
            f0=f0, v2s=v2s, start=start, frames_per_tile=frames_per_tile,
            pack_survivors=pack_survivors, radix=radix, layout=lay.value,
            bm_dtype=bm_dtype, interpret=interpret)
        bits = bits[:F]
    else:
        sel, amax = forward_frames(padded, trellis=trellis,
                                   frames_per_tile=frames_per_tile,
                                   pack_survivors=pack_survivors, radix=radix,
                                   layout=lay.value, bm_dtype=bm_dtype,
                                   interpret=interpret)
        sel, amax = sel[:F], amax[:F]                 # HBM round-trip
        if spec.parallel_tb:
            bits = parallel_traceback_frames(
                sel, amax, trellis, spec.v1, spec.f, spec.f0, spec.v2s,
                spec.start, packed=pack_survivors)
        else:
            bits = serial_traceback_frames(sel, amax, trellis, spec.v1,
                                           spec.f, packed=pack_survivors)
    if block_frames > 1:
        bits = merge_blocks(bits, block_frames)       # (F_in, f)
        assert bits.shape[0] == F_in
    return bits
