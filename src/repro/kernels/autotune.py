"""VMEM-budget-driven tile planner for the unified Pallas Viterbi kernel.

The seed hard-coded ``frames_per_tile=8``. That number is a *memory*
decision in disguise: each grid step of the unified kernel keeps the whole
per-tile working set (LLR block, compressed branch metrics, survivor
array, argmax trace, path metrics, output block) resident in VMEM, so
the right tile size is "as many frames as the VMEM budget allows" — more
frames per tile amortizes the fixed per-step scan overhead and gives
Mosaic a longer-lived block to pipeline DMA against (paper §IV-F,
"multiple frames per block").

One footprint model: the kernel's own arrays (``kernel_shapes``, which
``viterbi_unified`` allocates from) priced as Mosaic allocates them
(``mosaic_padded_bytes``): the trailing dim of every >=2D array padded
to 128 lanes and the second-to-last to 32/itemsize sublanes. The kernel
keeps frames on the lanes and flat stage-major rows on the sublanes, so
the padding is small and the 32x survivor packing survives it.

``plan_tiles`` picks the largest power-of-two tile whose footprint fits a
conservative budget (default 2 MiB of the ~16 MiB VMEM: leaves room for
double-buffered LLR DMA and concurrent tiles). ``plan_decode`` goes one
step further and returns the FULL plan the decode front-end executes —
tile, kernel knobs, and the per-chunk frame count the streaming front-end
(core/stream.py) feeds each device.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from ..core.framed import FrameSpec
from ..core.trellis import Trellis
from ..obs.tracer import get_tracer
from .block import resolve_block
from .packing import packed_width
from .tunedb import TUNE_DB, TuneDB, platform_id

__all__ = ["TilePlan", "DecodePlan", "mosaic_padded_bytes", "tile_ok",
           "kernel_shapes", "vmem_limit", "unified_vmem_bytes",
           "plan_tiles", "launch_tile", "plan_decode", "measure_plan",
           "DEFAULT_VMEM_BUDGET", "CANDIDATE_TILES", "MAX_FRAMES_PER_TILE",
           "LANES", "lane_chunks"]
# (subframe-geometry validation lives on FrameSpec.validate itself)

DEFAULT_VMEM_BUDGET = 2 * 1024 * 1024          # bytes, per grid step
#: Hard ceiling on tile candidates: candidates are generated from the
#: budget up to the frame count — the footprint is linear in FT, so the
#: loop in plan_tiles stops at the budget long before this backstop on any
#: realistic budget.
MAX_FRAMES_PER_TILE = 4096
CANDIDATE_TILES = tuple(8 << i for i in
                        range((MAX_FRAMES_PER_TILE // 8).bit_length()))

#: TPU lane width. The kernel puts frames on the lanes, so its compiled
#: frame tile is a multiple of this — or the whole batch.
LANES = 128


def _rup(n: int, m: int) -> int:
    return -(-n // m) * m


def mosaic_padded_bytes(shape: tuple, itemsize: int) -> int:
    """Bytes a real Mosaic allocation pays for ``shape``: last dim padded
    to 128 lanes, second-to-last to the dtype's sublane count (8 for 4-byte,
    16 for 2-byte, 32 for 1-byte), leading dims multiply. 1D arrays pay a
    whole minimum tile."""
    if len(shape) == 1:
        shape = (1,) + tuple(shape)
    lead = math.prod(shape[:-2]) if len(shape) > 2 else 1
    return (lead * _rup(shape[-2], 32 // itemsize) * _rup(shape[-1], 128)
            * itemsize)


def lane_chunks(frames_per_tile: int) -> tuple:
    """(C, lanes): how the kernel lays a frame tile on the lanes — C
    chunks of 128 frames when the tile is a multiple of 128 (Mosaic's
    strided loads need a 128-lane base array), else one chunk of the
    whole tile."""
    if frames_per_tile % LANES == 0:
        return frames_per_tile // LANES, LANES
    return 1, frames_per_tile


def tile_ok(frames_per_tile: int, max_frames: int | None) -> bool:
    """Whether the chip's tiling accepts this frame tile: the frame axis
    is the lane axis of every block, so a tile fills whole 128-lane rows,
    or covers the whole (padded) batch in one grid step."""
    return (frames_per_tile % LANES == 0
            or (max_frames is not None and frames_per_tile >= max_frames))


def kernel_shapes(trellis: Trellis, frame_len: int, f: int,
                  frames_per_tile: int, pack_survivors: bool) -> tuple:
    """The unified kernel's VMEM arrays for one grid step, as ((name,
    shape, dtype), ...): its two pipelined blocks (LLRs in, bits out),
    then its scratch in the kernel's argument order. The kernel allocates
    from this, so the footprint model cannot drift from it."""
    S = trellis.num_states
    half = 1 << (trellis.beta - 1)
    L = frame_len
    C, lanes = lane_chunks(frames_per_tile)
    R = packed_width(S) if pack_survivors else S       # survivor rows/stage
    return (
        ("llr_block", (C, L * trellis.beta, lanes), jnp.float32),
        ("out_block", (C, f, lanes), jnp.int32),
        ("sel_survivors", (C, L * R, lanes), jnp.int32),
        ("amax", (C, L, lanes), jnp.int32),            # per-stage argmax
        ("bm_compressed", (C, half * L, lanes), jnp.float32),  # eq. 9
        ("path_metrics", (C, S, lanes), jnp.float32),
    )


#: how many of ``kernel_shapes``' leading arrays are pipelined blocks
_BLOCKS = 2


def _padded(shapes) -> tuple:
    return tuple((n, mosaic_padded_bytes(s, jnp.dtype(d).itemsize))
                 for n, s, d in shapes)


def vmem_limit(shapes) -> int:
    """Scoped-VMEM limit for the kernel with ``kernel_shapes`` ``shapes``:
    the padded scratch plus the double-buffered pipeline blocks, with
    headroom; at least 32 MiB and at most 120 MiB of the chip's 128
    MiB."""
    padded = [b for _, b in _padded(shapes)]
    need = 2 * sum(padded[:_BLOCKS]) + sum(padded[_BLOCKS:])
    return int(min(max(need + need // 4 + (4 << 20), 32 << 20), 120 << 20))


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Chosen tile size + the footprint that justified it."""
    frames_per_tile: int
    vmem_bytes: int
    breakdown: tuple          # ((name, bytes), ...) for reports/debugging
    budget: int
    vmem_limit: int           # scoped-VMEM limit the kernel asks for

    def utilization(self) -> float:
        return self.vmem_bytes / self.budget

    def cache_key(self) -> tuple:
        """The knobs that select a distinct compiled kernel — the tile's
        contribution to the compiled-plan cache key (serve.plan_cache).
        Footprint/budget bookkeeping is deliberately excluded: two plans
        that picked the same knobs compile to the same kernel."""
        return (int(self.frames_per_tile),)


def unified_vmem_bytes(trellis: Trellis, spec: FrameSpec,
                       frames_per_tile: int, *,
                       pack_survivors: bool = False):
    """(total_bytes, breakdown) of one unified-kernel grid step under
    Mosaic padding (``kernel_shapes``; the traceback geometry and radix
    do not change it)."""
    breakdown = _padded(kernel_shapes(trellis, spec.frame_len, spec.f,
                                      frames_per_tile, pack_survivors))
    return sum(b for _, b in breakdown), breakdown


def _tile(trellis: Trellis, spec: FrameSpec, frames_per_tile: int,
          pack_survivors: bool, vmem_budget: int) -> TilePlan:
    shapes = kernel_shapes(trellis, spec.frame_len, spec.f,
                           frames_per_tile, pack_survivors)
    breakdown = _padded(shapes)
    return TilePlan(int(frames_per_tile), sum(b for _, b in breakdown),
                    breakdown, vmem_budget, vmem_limit(shapes))


def plan_tiles(trellis: Trellis, spec: FrameSpec, *,
               pack_survivors: bool = False,
               vmem_budget: int = DEFAULT_VMEM_BUDGET,
               max_frames: int | None = None) -> TilePlan:
    """Pick frames_per_tile for one kernel configuration from a VMEM budget.

    Returns the largest candidate tile that fits ``vmem_budget``; the
    smallest candidate is returned even when over budget (the kernel still
    runs — headroom just shrinks). Candidates are powers of two generated
    from the budget up to the frame count: growth stops at the first
    over-budget tile, ``max_frames`` caps the tile near the actual frame
    count so short streams don't decode mostly padding, and only the
    MAX_FRAMES_PER_TILE backstop bounds an effectively unlimited budget.
    """
    spec.validate()
    candidates = list(CANDIDATE_TILES)
    if max_frames is not None:
        # smallest candidate covering the stream in one tile is enough
        cap = next((c for c in candidates if c >= max_frames),
                   candidates[-1])
        candidates = [c for c in candidates if c <= cap]
    candidates = [c for c in candidates if tile_ok(c, max_frames)]

    best = None
    for ft in candidates:
        plan = _tile(trellis, spec, ft, pack_survivors, vmem_budget)
        if plan.vmem_bytes <= vmem_budget or best is None:
            best = plan
        if plan.vmem_bytes > vmem_budget:
            break
    return best


def launch_tile(trellis: Trellis, spec: FrameSpec, frames: int | None, *,
                frames_per_tile: int | str = "auto",
                pack_survivors: bool = True,
                vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TilePlan:
    """The tile a kernel launch of ``frames`` frames runs: under
    ``"auto"`` the planner's choice for that many frames
    (``plan_tiles``), else the pinned ``frames_per_tile`` with its
    footprint."""
    if frames_per_tile == "auto":
        return plan_tiles(trellis, spec, pack_survivors=pack_survivors,
                          vmem_budget=vmem_budget, max_frames=frames)
    return _tile(trellis, spec, frames_per_tile, pack_survivors,
                 vmem_budget)


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """The full configuration the decode front-end executes: kernel knobs
    (tile) plus the streaming geometry (chunk sizing across devices).
    ``block_frames``/``overlap`` are the intra-frame block-parallel knobs
    (kernels/block.py), always stored RESOLVED (1/0 = blocking off); when
    on, ``tile`` is budgeted against the derived per-block spec — the
    short frames the kernel actually sees — and ``frames_per_tile``
    counts those blocks, not outer frames."""
    tile: TilePlan
    pack_survivors: bool
    radix: int
    chunk_frames: int         # frames the stream front-end batches per chunk
    num_devices: int          # chunk_frames is a multiple of tiles x devices
    block_frames: int = 1     # intra-frame blocks per frame (1 = off)
    overlap: int = 0          # per-block training/truncation stages

    @property
    def frames_per_tile(self) -> int:
        return self.tile.frames_per_tile

    def kernel_kwargs(self) -> dict:
        """kwargs for ops.viterbi_decode_frames, ready to splat."""
        return dict(frames_per_tile=self.tile.frames_per_tile,
                    pack_survivors=self.pack_survivors, radix=self.radix,
                    block_frames=self.block_frames, overlap=self.overlap)

    def cache_key(self) -> tuple:
        """Stable, hashable identity of the full plan: everything that
        changes the compiled decode (kernel knobs — including the block
        decomposition, which changes the decoded BITS) or the launch
        geometry (chunk sizing across devices). Together with (trellis,
        spec, nframes) this keys the compiled-plan cache and the serve
        layer's session buckets."""
        return (*self.tile.cache_key(), bool(self.pack_survivors),
                int(self.radix), int(self.chunk_frames),
                int(self.num_devices), int(self.block_frames),
                int(self.overlap))

    def fingerprint(self) -> str:
        """Short hex digest of cache_key() — a human-greppable bucket id
        for metrics rows and benchmark records."""
        import hashlib
        return hashlib.sha1(repr(self.cache_key()).encode()).hexdigest()[:10]


def measure_plan(trellis: Trellis, spec: FrameSpec, plan: DecodePlan, *,
                 reps: int = 2, frames: int | None = None,
                 interpret: bool | None = None) -> dict:
    """Time one DecodePlan with real launches of the kernel it selects.

    One warm-up launch pays the compile, then ``reps`` timed launches keep
    the minimum (the least-noisy estimator on a shared machine — same
    discipline as benchmarks/throughput.py). The launch geometry is the
    plan's own: ``frames`` defaults to ``plan.chunk_frames``, the chunk the
    streaming front-end would actually feed this plan, so the record prices
    padding and pipelining exactly as production launches would.

    ``interpret`` defaults to True only on the CPU backend (Pallas kernels
    need the interpreter there); on a real accelerator the launch is
    compiled — that is the whole point of measuring.

    Returns the tune-DB record: ``{ms, mbps, frames, reps, interpret,
    fingerprint}``. Pure timing — callers decide whether to persist it
    (``plan_decode(measure=True)`` does, via TuneDB).
    """
    import time as _time

    import numpy as np

    from . import ops            # lazy: ops imports this module at top level

    if interpret is None:
        interpret = platform_id()["backend"] == "cpu"
    F = int(frames if frames is not None else plan.chunk_frames)
    rng = np.random.default_rng(0)
    llr = jnp.asarray(rng.standard_normal(
        (F, spec.frame_len, trellis.beta)).astype(np.float32))
    kw = plan.kernel_kwargs()

    def launch():
        return ops.viterbi_decode_frames(llr, trellis, spec,
                                         interpret=bool(interpret), **kw)

    launch().block_until_ready()             # compile + warm-up
    best = math.inf
    for _ in range(max(1, int(reps))):
        t0 = _time.perf_counter()
        launch().block_until_ready()
        best = min(best, _time.perf_counter() - t0)
    bits = F * spec.f
    return {"ms": best * 1e3, "mbps": bits / best / 1e6, "frames": F,
            "reps": int(reps), "interpret": bool(interpret),
            "fingerprint": plan.fingerprint()}


def _measure_candidates(trellis: Trellis, plan_spec: FrameSpec,
                        analytic: DecodePlan, *, pack_survivors: bool,
                        radix: int, vmem_budget: int, eff_max,
                        num_devices: int, bf: int, ov: int, chunk_frames,
                        top_k: int):
    """Top-k candidate plans for the timing pass: the analytic winner and
    the half/double tile variants of it (the footprint model is linear,
    but launch overhead vs pipelining is not). Deduped by cache_key;
    analytic order kept so ties resolve to the model's choice."""
    tiles = [analytic.tile]
    ft0 = analytic.tile.frames_per_tile
    for ft in (ft0 // 2, ft0 * 2):
        if (CANDIDATE_TILES[0] <= ft <= MAX_FRAMES_PER_TILE
                and tile_ok(ft, eff_max)):
            tiles.append(_tile(trellis, plan_spec, ft, pack_survivors,
                               vmem_budget))
    out, seen = [], set()
    for t in tiles:
        cf = (int(chunk_frames) if chunk_frames is not None
              else 2 * max(1, t.frames_per_tile // bf) * num_devices)
        p = DecodePlan(t, pack_survivors, radix, cf, num_devices, bf, ov)
        k = p.cache_key()
        if k not in seen:
            seen.add(k)
            out.append(p)
    return out[:max(1, int(top_k))]


def plan_decode(trellis: Trellis, spec: FrameSpec, *,
                pack_survivors: bool = True, radix: int = 4,
                vmem_budget: int = DEFAULT_VMEM_BUDGET, num_devices: int = 1,
                chunk_frames: int | None = None,
                max_frames: int | None = None,
                frames_per_tile: int | None = None,
                block_frames: int | str = 1,
                overlap: int | None = None,
                measure: bool = False, tunedb: TuneDB | None = None,
                measure_top_k: int = 3, measure_reps: int = 2,
                measure_frames: int | None = None) -> DecodePlan:
    """Plan the whole decode: tile, kernel knobs and chunk geometry.

    ``chunk_frames`` defaults to two tiles per device so the streaming
    front-end can double-buffer. ``frames_per_tile`` pins the tile
    instead of autotuning it (the serve layer passes a session's explicit
    knob through here so the plan — and its padding accounting — matches
    the kernel that actually launches).

    ``block_frames``/``overlap`` are the intra-frame block-parallel knobs
    (kernels/block.py): an int, or ``"auto"`` to engage blocking past
    BLOCK_LEN_THRESHOLD kept stages. When blocking is on, the tile is
    budgeted against the DERIVED per-block spec — the planner trades
    frames-per-tile against blocks-per-frame under the same VMEM model,
    so a long frame that only fits a handful of sequential scans per tile
    becomes many short blocks that fill the tile instead. Tile counts and
    ``frames_per_tile`` are then in block units; ``chunk_frames`` stays in
    OUTER frames (what core/stream.py slices), defaulting to two tiles'
    worth of whole frames per device.

    ``measure=True`` adds the on-device timing pass: the top-k analytic
    candidates (``_measure_candidates``) are timed with real launches
    (``measure_plan`` — compiled on accelerators, interpret on CPU) and
    the plan with the highest measured Mb/s wins. Timings are persisted
    to the disk-backed tune DB (kernels/tunedb.py; pass ``tunedb=`` to
    use a non-default instance) keyed by ``DecodePlan.fingerprint()`` x
    platform identity, so a plan is measured once per (hardware, code)
    pair and every later process reuses the cached timing with zero
    re-measurement (``tunedb_hits`` tracer counters prove it).

    Every call runs under a ``plan_decode`` tracing span whose attributes
    carry the chosen plan (tile, chunk geometry, block decomposition) and
    the predicted VMEM footprint vs budget — and, under ``measure=True``,
    the measured ms/Mb/s next to the predicted bytes plus how many
    candidates came from cache vs fresh measurement.
    """
    with get_tracer().span("plan_decode") as sp:
        spec.validate()
        bf, ov = resolve_block(trellis, spec, block_frames, overlap)
        plan_spec = spec.blocked(bf, ov) if bf > 1 else spec
        eff_max = (max_frames * bf if (max_frames is not None and bf > 1)
                   else max_frames)
        if frames_per_tile is not None:
            tile = launch_tile(
                trellis, plan_spec, None, frames_per_tile=frames_per_tile,
                pack_survivors=pack_survivors, vmem_budget=vmem_budget)
        else:
            tile = plan_tiles(trellis, plan_spec,
                              pack_survivors=pack_survivors,
                              vmem_budget=vmem_budget, max_frames=eff_max)
        chunk = (int(chunk_frames) if chunk_frames is not None
                 else 2 * max(1, tile.frames_per_tile // bf) * num_devices)
        plan = DecodePlan(tile, pack_survivors, radix, chunk,
                          num_devices, bf, ov)
        if measure:
            db = tunedb if tunedb is not None else TUNE_DB
            if frames_per_tile is not None:
                candidates = [plan]       # pinned tile: measure + record it
            else:
                candidates = _measure_candidates(
                    trellis, plan_spec, plan,
                    pack_survivors=pack_survivors, radix=radix,
                    vmem_budget=vmem_budget, eff_max=eff_max,
                    num_devices=num_devices, bf=bf, ov=ov,
                    chunk_frames=chunk_frames, top_k=measure_top_k)
            plat = platform_id()
            records, fresh = [], 0
            for cand in candidates:
                rec = db.get(cand.fingerprint(), plat)
                if rec is None:
                    rec = measure_plan(trellis, spec, cand,
                                       reps=measure_reps,
                                       frames=measure_frames)
                    db.put(cand.fingerprint(), rec, plat)
                    db.record_measure()
                    fresh += 1
                records.append((cand, rec))
            analytic_fp = plan.fingerprint()
            plan, best = max(records,
                             key=lambda pr: pr[1].get("mbps", 0.0))
            tile = plan.tile
            sp.set(measured_ms=round(float(best["ms"]), 4),
                   measured_mbps=round(float(best["mbps"]), 4),
                   measure_candidates=len(records), measure_new=fresh,
                   measure_cached=len(records) - fresh,
                   analytic_fingerprint=analytic_fp)
        sp.set(frames_per_tile=tile.frames_per_tile,
               chunk_frames=int(plan.chunk_frames),
               num_devices=int(num_devices), block_frames=int(bf),
               overlap=int(ov), vmem_bytes=tile.vmem_bytes,
               vmem_budget=tile.budget,
               fits=tile.vmem_bytes <= tile.budget,
               fingerprint=plan.fingerprint())
        return plan
