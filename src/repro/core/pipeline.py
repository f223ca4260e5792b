"""End-to-end decode API: the paper's full receiver path.

depuncture -> frame -> unified decode (Pallas kernel or pure-JAX reference)
-> stitch. This is the composable module the rest of the framework (examples,
benchmarks, multi-pod launch, the streaming front-end in core/stream.py)
calls. ``make_frame_decoder`` exposes the frames->bits core so front-ends
that do their own framing (chunked streams, sharded decode) share one
backend dispatch.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .framed import FrameSpec, decode_frame, flatten_bits, frame_llr
from .puncture import check_alignment, check_rate, depuncture, unpunctured
from .sanitize import LLR_CLIP as _LLR_CLIP
from .trellis import Trellis, STD_K7

__all__ = ["DecoderConfig", "make_decoder", "make_frame_decoder",
           "platform_defaults"]


def platform_defaults() -> dict:
    """``backend`` and ``interpret`` where the caller leaves them
    (DecoderConfig, ops.viterbi_decode_frames), from
    ``jax.default_backend()``: on a TPU the compiled unified kernel;
    elsewhere the pure-JAX reference, with the kernel, when asked for,
    interpreted — the same program Mosaic compiles. Callers read only
    the keys they need, so a dict with more keys is fine."""
    if jax.default_backend() == "tpu":
        return {"backend": "kernel", "interpret": False}
    return {"backend": "reference", "interpret": True}


def _platform(knob: str):
    return dataclasses.field(default_factory=lambda: platform_defaults()[knob])


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """Everything needed to build a decode function.

    The kernel knobs (pack_survivors / radix / frames_per_tile) default to
    the best-known configuration — bit-packed survivors, two trellis stages
    per scan step, VMEM-budget-autotuned tile size. Every combination is
    bit-identical to the reference backend, so these are pure perf knobs
    (set radix=2, pack_survivors=False, frames_per_tile=8 for the seed
    kernel behavior).

    ``backend`` ('reference' or 'kernel') and ``interpret`` default from
    the platform when the config is built (``platform_defaults``).

    ``rate`` names the puncturing pattern over the trellis's beta
    generators; None (the default) is the unpunctured ``1/beta`` —
    ``"1/2"`` on a rate-1/2 trellis, ``"1/3"`` on a rate-1/3 one. A
    pattern whose row count is not beta raises ValueError.

    ``renorm_every`` is the path-metric renormalization period: 1
    (default) subtracts the stage max every ACS stage — the historical
    behavior and what the Pallas kernels always do; N>1 amortizes the max
    reduction over N stages, 0 disables it (reference backend only, for
    the renormalization bit-identity gate in tests/test_faults.py).

    ``block_frames``/``overlap`` engage the intra-frame block-parallel
    decode (kernels/block.py): each frame's f kept stages split into
    block_frames blocks of f/block_frames stages carrying an
    overlap-stage training/truncation region on each side, decoded in
    parallel and merged by truncation. ``"auto"`` engages blocking only
    past BLOCK_LEN_THRESHOLD kept stages; ``overlap=None`` takes the
    ~5*constraint-length default. The one knob that is not bit-exact
    (truncated-traceback approximation, BER-gated to 1e-3 in
    tests/test_block.py) — applied by both backends, reference included,
    so kernel-vs-reference stays bit-identical under blocking.
    """
    trellis: Trellis = STD_K7
    spec: FrameSpec = FrameSpec()
    rate: str | None = None        # puncturing pattern; None = 1/beta
    backend: str = _platform("backend")   # 'reference' | 'kernel'
    interpret: bool = _platform("interpret")   # Pallas interpret mode
    pack_survivors: bool = True    # bit-pack survivors 32x (kernel)
    radix: int = 4                 # 2 | 4 trellis stages per ACS step
    frames_per_tile: int | str = "auto"   # tile size, or VMEM-planned
    renorm_every: int = 1          # path-metric renormalization period
    block_frames: int | str = 1    # intra-frame blocks per frame, or 'auto'
    overlap: int | None = None     # block training/truncation stages

    def __post_init__(self):
        object.__setattr__(self, "rate",
                           check_rate(self.rate, self.trellis.beta))
        if self.punctured:
            check_alignment(self.spec.f, self.spec.v1, self.spec.v2, self.rate)
        if self.radix not in (2, 4):
            raise ValueError(f"radix must be 2 or 4, got {self.radix}")
        if self.backend not in ("reference", "kernel"):
            raise ValueError(f"backend must be 'reference' or 'kernel', "
                             f"got {self.backend!r}")
        if self.renorm_every < 0:
            raise ValueError(f"renorm_every must be >= 0, "
                             f"got {self.renorm_every}")
        if self.renorm_every != 1 and self.backend != "reference":
            raise ValueError(
                "renorm_every != 1 requires backend='reference' (the "
                "Pallas kernels renormalize every stage unconditionally)")
        if not (self.block_frames == "auto"
                or (isinstance(self.block_frames, int)
                    and self.block_frames >= 1)):
            raise ValueError(
                f"block_frames must be 'auto' or an int >= 1, "
                f"got {self.block_frames!r}")
        if self.overlap is not None and self.overlap < 0:
            raise ValueError(f"overlap must be >= 0, got {self.overlap}")
        if (self.block_frames not in (1, "auto")
                or self.overlap is not None):
            # explicit knobs: fail at config time with the geometry error,
            # not at first decode (``"auto"`` self-limits to valid splits)
            from ..kernels.block import resolve_block
            resolve_block(self.trellis, self.spec, self.block_frames,
                          self.overlap)

    @property
    def punctured(self) -> bool:
        """Whether ``rate`` drops symbols (pushes are then the raw
        punctured stream, depunctured in-stream)."""
        return self.rate != unpunctured(self.trellis.beta)


def _build_frame_decoder(cfg: DecoderConfig):
    """Build the backend-dispatch closure (uncached — see
    make_frame_decoder / serve.plan_cache for the shared entry point)."""
    from ..kernels.block import merge_blocks, reframe_blocks, resolve_block
    bf, ov = resolve_block(cfg.trellis, cfg.spec, cfg.block_frames,
                           cfg.overlap)
    if cfg.backend == "reference":
        if bf > 1:
            # the reference path applies the SAME block decomposition as
            # the kernels so kernel-vs-reference stays bit-identical (and
            # serve degrade/failover to reference is decode-equivalent)
            sub = cfg.spec.blocked(bf, ov)

            def decode_frames(frames):
                blocks = reframe_blocks(frames, cfg.spec, bf, ov)
                bits = jax.vmap(
                    lambda fr: decode_frame(fr, cfg.trellis, sub,
                                            cfg.renorm_every))(blocks)
                return merge_blocks(bits, bf)
        else:
            def decode_frames(frames):
                return jax.vmap(
                    lambda fr: decode_frame(fr, cfg.trellis, cfg.spec,
                                            cfg.renorm_every))(frames)
    else:
        from ..kernels import ops as kops

        def decode_frames(frames):
            return kops.viterbi_decode_frames(
                frames, cfg.trellis, cfg.spec,
                frames_per_tile=cfg.frames_per_tile,
                pack_survivors=cfg.pack_survivors, radix=cfg.radix,
                block_frames=bf, overlap=ov, interpret=cfg.interpret)
    return decode_frames


def make_frame_decoder(cfg: DecoderConfig):
    """Returns decode_frames(frames (F, L, beta)) -> (F, f) bits.

    The backend-dispatch core shared by make_decoder, the streaming
    front-end (core/stream.py) and the sharded decoder (distributed/
    stream.py). Not jitted here — callers jit the enclosing computation.
    Memoized per cfg in the process-global compiled-plan cache
    (serve.plan_cache): every caller gets the SAME closure, so enclosing
    jits share their trace cache across tenant churn.
    """
    from ..serve.plan_cache import PLAN_CACHE
    return PLAN_CACHE.frame_decoder(cfg)


def make_decoder(cfg: DecoderConfig):
    """Returns decode(llr_or_stream, n) -> (n,) bits, jitted."""
    _decode_frames = make_frame_decoder(cfg)

    @partial(jax.jit, static_argnums=(1,))
    def decode(stream: jax.Array, n: int) -> jax.Array:
        """stream: punctured soft symbols (m,) for a punctured rate, or
        (n, beta)."""
        # in-graph input hardening (core.sanitize): NaN/Inf -> neutral
        # zero, |llr| > clip -> ±clip. Identity on clean in-range inputs,
        # so the clean path stays bit-identical.
        stream = jnp.clip(
            jnp.where(jnp.isfinite(stream), stream, jnp.zeros_like(stream)),
            -_LLR_CLIP, _LLR_CLIP)
        if cfg.punctured:
            llr = depuncture(stream, cfg.rate, n)
        else:
            llr = stream if stream.ndim == 2 else stream.reshape(n, -1)
        frames = frame_llr(llr, cfg.spec)             # (F, L, beta)
        bits = _decode_frames(frames)                 # (F, f)
        return flatten_bits(bits)[:n]

    return decode
