"""VMEM-budget-driven tile planner for the Pallas Viterbi kernels.

The seed hard-coded ``frames_per_tile=8``. That number is a *memory*
decision in disguise: each grid step of the unified kernel keeps the whole
per-tile working set (LLR block, compressed branch metrics, survivor
array, argmax trace, traceback bits, output block) resident in VMEM, so
the right tile size is "as many frames as the VMEM budget allows" — more
frames per tile amortizes the fixed per-step scan overhead and gives
Mosaic a longer-lived block to pipeline DMA against (paper §IV-F,
"multiple frames per block").

Two accounting models:

* **logical** bytes — element counts x itemsize. This is what the scratch
  *specs* declare, what interpret mode allocates, and the honest budget
  for the GPU shared-memory target the paper describes.
* **mosaic** bytes (``mosaic_padded_bytes``) — what a real TPU allocates:
  the trailing dim of every >=2D array is padded to 128 lanes and the
  second-to-last to 32/itemsize sublanes. Under this model the lane
  layout's packed ``(.., W=2)`` survivors balloon 64x, which is exactly
  why the sublane layout (frames on lanes, flat stage-major scratches)
  exists — see viterbi_unified.py's budget table.

``plan_tiles`` picks the largest power-of-two tile whose footprint fits a
conservative budget (default 2 MiB of the ~16 MiB VMEM: leaves room for
double-buffered LLR DMA and concurrent tiles), for either kernel
(``unified=False`` uses the split kernel's smaller per-step footprint),
either layout, and either branch-metric dtype. ``plan_decode`` goes one
step further and returns the FULL plan the decode front-end executes —
kernel, layout (``'auto'`` compares both under mosaic accounting), tile,
and the per-chunk frame count the streaming front-end (core/stream.py)
feeds each device.
"""
from __future__ import annotations

import dataclasses
import math

from ..core.framed import FrameSpec
from ..core.trellis import Trellis
from ..obs.tracer import get_tracer
from .block import resolve_block
from .packing import Layout, packed_width
from .tunedb import TUNE_DB, TuneDB, platform_id

__all__ = ["TilePlan", "DecodePlan", "mosaic_padded_bytes", "tile_ok",
           "unified_vmem_bytes", "split_vmem_bytes", "plan_tiles",
           "launch_tile", "plan_decode", "measure_plan",
           "DEFAULT_VMEM_BUDGET", "CANDIDATE_TILES", "MAX_FRAMES_PER_TILE",
           "LANES", "lane_chunks"]
# (subframe-geometry validation lives on FrameSpec.validate itself)

DEFAULT_VMEM_BUDGET = 2 * 1024 * 1024          # bytes, per grid step
#: Hard ceiling on tile candidates. The old 256 cap (ROADMAP open item) is
#: lifted: candidates are generated from the budget up to the frame count —
#: the footprint models are linear in FT, so the loop in plan_tiles stops
#: at the budget long before this backstop on any realistic budget.
MAX_FRAMES_PER_TILE = 4096
CANDIDATE_TILES = tuple(8 << i for i in
                        range((MAX_FRAMES_PER_TILE // 8).bit_length()))

_BM_ITEMSIZE = {"float32": 4, "bfloat16": 2}

#: TPU lane width. The sublane layout puts frames on the lanes, so its
#: compiled frame tile is a multiple of this — or the whole batch.
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


def _bm_itemsize(bm_dtype) -> int:
    try:
        return _BM_ITEMSIZE[str(bm_dtype)]
    except KeyError:
        raise ValueError(f"bm_dtype must be one of {sorted(_BM_ITEMSIZE)}, "
                         f"got {bm_dtype!r}")


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Chosen tile size + the footprint that justified it."""
    frames_per_tile: int
    vmem_bytes: int
    breakdown: tuple          # ((name, bytes), ...) for reports/debugging
    budget: int
    kernel: str = "unified"   # 'unified' | 'split'
    layout: Layout = Layout.LANE
    bm_dtype: str = "float32"
    mosaic: bool = False      # padded (hardware) or logical accounting

    def utilization(self) -> float:
        return self.vmem_bytes / self.budget

    def cache_key(self) -> tuple:
        """The knobs that select a distinct compiled kernel — the tile's
        contribution to the compiled-plan cache key (serve.plan_cache).
        Footprint/budget bookkeeping is deliberately excluded: two plans
        that picked the same knobs compile to the same kernel."""
        return (self.kernel, int(self.frames_per_tile),
                Layout(self.layout).value, str(self.bm_dtype))


def _geometry(spec: FrameSpec):
    """(f0, v2s) as the kernel sees them (serial tb = one full subframe)."""
    if spec.parallel_tb:
        return spec.f0, spec.v2s
    return spec.f, spec.v2


def _shapes_unified(trellis: Trellis, spec: FrameSpec, FT: int,
                    pack: bool, layout: Layout, bm_isz: int):
    """((name, shape, itemsize), ...) mirroring viterbi_unified.py exactly."""
    S = trellis.num_states
    beta = trellis.beta
    half = 1 << (beta - 1)
    L = spec.frame_len
    W = packed_width(S)
    f0, v2s = _geometry(spec)
    nsub = spec.f // f0
    if layout is Layout.SUBLANE:
        C, lanes = lane_chunks(FT)
        return (
            ("llr_block", (C, L * beta, lanes), 4),
            ("bm_compressed", (C, half * L, lanes), bm_isz),
            ("sel_survivors", (C, L * (W if pack else S), lanes), 4),
            ("amax", (C, L, lanes), 4),
            ("path_metrics", (C, S, lanes), 4),
            ("out_block", (C, spec.f, lanes), 4),
        )
    sel_w = W if pack else S
    return (
        ("llr_block", (FT, L, beta), 4),
        ("bm_compressed", (L, FT, half), bm_isz),
        ("sel_survivors", (L, FT, sel_w), 4),
        ("amax", (L, FT), 4),
        ("tb_bits", (f0 + v2s, nsub, FT), 4),
        ("out_block", (FT, spec.f), 4),
    )


def _shapes_split(trellis: Trellis, spec: FrameSpec, FT: int,
                  pack: bool, layout: Layout, bm_isz: int):
    """((name, shape, itemsize), ...) mirroring viterbi_fwd.py: the per-step
    working set is the LLR block, the bm scratch, and the staged sel/amax
    output blocks — no survivor scratch and no traceback arrays (those live
    in HBM / run as a separate JAX step). Lane layout only, as the kernel."""
    if layout is not Layout.LANE:
        raise ValueError("the split kernel runs only in the lane layout")
    S = trellis.num_states
    beta = trellis.beta
    half = 1 << (beta - 1)
    L = spec.frame_len
    sel = ((FT, L, packed_width(S)), 4) if pack else ((FT, L, S), 1)
    return (
        ("llr_block", (FT, L, beta), 4),
        ("bm_compressed", (L, FT, half), bm_isz),
        ("sel_stream", *sel),
        ("amax_stream", (FT, L), 4),
    )


def _layouts(unified: bool) -> tuple:
    """The layouts ``layout='auto'`` weighs: the split kernel has one."""
    return (Layout.LANE, Layout.SUBLANE) if unified else (Layout.LANE,)


def lane_chunks(frames_per_tile: int) -> tuple:
    """(C, lanes): how the sublane kernels lay a frame tile on the lanes —
    C chunks of 128 frames when the tile is a multiple of 128 (Mosaic's
    strided loads need a 128-lane base array), else one chunk of the
    whole tile."""
    if frames_per_tile % LANES == 0:
        return frames_per_tile // LANES, LANES
    return 1, frames_per_tile


def tile_ok(layout, frames_per_tile: int, max_frames: int | None) -> bool:
    """Whether the chip's tiling accepts this frame tile: the sublane
    layout's frame axis is the lane axis of every block, so a tile fills
    whole 128-lane rows, or covers the whole (padded) batch in one grid
    step. The lane layout runs in interpret mode only and takes any."""
    return (Layout(layout) is not Layout.SUBLANE
            or frames_per_tile % LANES == 0
            or (max_frames is not None and frames_per_tile >= max_frames))


def _footprint(shapes, mosaic: bool):
    if mosaic:
        breakdown = tuple((n, mosaic_padded_bytes(s, i)) for n, s, i in shapes)
    else:
        breakdown = tuple((n, math.prod(s) * i) for n, s, i in shapes)
    return sum(b for _, b in breakdown), breakdown


def _resolve(layout, mosaic):
    layout = Layout(layout)
    if mosaic is None:
        # the sublane layout exists to survive hardware padding, so it is
        # judged by it; the lane layout keeps the interpret-mode (logical)
        # model that PR-1 plans were made with
        mosaic = layout is Layout.SUBLANE
    return layout, mosaic


def unified_vmem_bytes(trellis: Trellis, spec: FrameSpec,
                       frames_per_tile: int, *, pack_survivors: bool = False,
                       radix: int = 2, layout=Layout.LANE,
                       bm_dtype: str = "float32", mosaic: bool | None = None):
    """(total_bytes, breakdown) of one unified-kernel grid step.

    Mirrors the scratch_shapes + block specs in viterbi_unified.py exactly;
    ``radix`` does not change the footprint (the fused BM row is a
    transient concatenation), it is accepted so call sites can pass the
    full kernel config through one interface. ``mosaic=None`` defaults to
    padded accounting for the sublane layout, logical for lane.
    """
    del radix
    layout, mosaic = _resolve(layout, mosaic)
    shapes = _shapes_unified(trellis, spec, frames_per_tile, pack_survivors,
                             layout, _bm_itemsize(bm_dtype))
    return _footprint(shapes, mosaic)


def split_vmem_bytes(trellis: Trellis, spec: FrameSpec,
                     frames_per_tile: int, *, pack_survivors: bool = False,
                     radix: int = 2, layout=Layout.LANE,
                     bm_dtype: str = "float32", mosaic: bool | None = None):
    """(total_bytes, breakdown) of one split (forward) kernel grid step —
    the smaller footprint plan_tiles(unified=False) budgets against."""
    del radix
    layout, mosaic = _resolve(layout, mosaic)
    shapes = _shapes_split(trellis, spec, frames_per_tile, pack_survivors,
                           layout, _bm_itemsize(bm_dtype))
    return _footprint(shapes, mosaic)


def plan_tiles(trellis: Trellis, spec: FrameSpec, *,
               pack_survivors: bool = False, radix: int = 2,
               vmem_budget: int = DEFAULT_VMEM_BUDGET,
               max_frames: int | None = None, unified: bool = True,
               layout=Layout.LANE, bm_dtype: str = "float32",
               mosaic: bool | None = None) -> TilePlan:
    """Pick frames_per_tile for one kernel configuration from a VMEM budget.

    Returns the largest candidate tile that fits ``vmem_budget``; the
    smallest candidate is returned even when over budget (the kernel still
    runs — headroom just shrinks). Candidates are powers of two generated
    from the budget up to the frame count: growth stops at the first
    over-budget tile, ``max_frames`` caps the tile near the actual frame
    count so short streams don't decode mostly padding, and only the
    MAX_FRAMES_PER_TILE backstop bounds an effectively unlimited budget
    (the 256 cap of PR 1 is gone — sublane plans beyond 256 frames are
    real configurations at larger budgets).
    ``unified=False`` budgets the split (forward-only) kernel's footprint.
    """
    spec.validate()
    layout, mosaic = _resolve(layout, mosaic)
    model = unified_vmem_bytes if unified else split_vmem_bytes
    candidates = list(CANDIDATE_TILES)
    if max_frames is not None:
        # smallest candidate covering the stream in one tile is enough
        cap = next((c for c in candidates if c >= max_frames),
                   candidates[-1])
        candidates = [c for c in candidates if c <= cap]
    candidates = [c for c in candidates if tile_ok(layout, c, max_frames)]

    best = None
    for ft in candidates:
        total, breakdown = model(
            trellis, spec, ft, pack_survivors=pack_survivors, radix=radix,
            layout=layout, bm_dtype=bm_dtype, mosaic=mosaic)
        plan = TilePlan(ft, total, breakdown, vmem_budget,
                        "unified" if unified else "split", layout,
                        str(bm_dtype), mosaic)
        if total <= vmem_budget or best is None:
            best = plan
        if total > vmem_budget:
            break
    return best


def launch_tile(trellis: Trellis, spec: FrameSpec, frames: int | None, *,
                frames_per_tile: int | str = "auto", unified: bool = True,
                pack_survivors: bool = True, radix: int = 4,
                layout=Layout.SUBLANE, bm_dtype: str = "float32",
                vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TilePlan:
    """The tile a kernel launch of ``frames`` frames runs: under
    ``"auto"`` the planner's choice for that many frames
    (``plan_tiles``), else the pinned ``frames_per_tile`` with its
    footprint."""
    if frames_per_tile == "auto":
        return plan_tiles(trellis, spec, pack_survivors=pack_survivors,
                          radix=radix, vmem_budget=vmem_budget,
                          max_frames=frames, unified=unified, layout=layout,
                          bm_dtype=bm_dtype)
    layout, mosaic = _resolve(layout, None)
    model = unified_vmem_bytes if unified else split_vmem_bytes
    total, breakdown = model(trellis, spec, frames_per_tile,
                             pack_survivors=pack_survivors, radix=radix,
                             layout=layout, bm_dtype=bm_dtype, mosaic=mosaic)
    return TilePlan(int(frames_per_tile), total, breakdown, vmem_budget,
                    "unified" if unified else "split", layout,
                    str(bm_dtype), mosaic)


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
    def unified(self) -> bool:
        return self.tile.kernel == "unified"

    @property
    def frames_per_tile(self) -> int:
        return self.tile.frames_per_tile

    def kernel_kwargs(self) -> dict:
        """kwargs for ops.viterbi_decode_frames, ready to splat."""
        return dict(unified=self.unified,
                    frames_per_tile=self.tile.frames_per_tile,
                    pack_survivors=self.pack_survivors, radix=self.radix,
                    layout=self.tile.layout.value,
                    bm_dtype=self.tile.bm_dtype,
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
    import jax.numpy as jnp

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


def _tile_at(trellis: Trellis, plan_spec: FrameSpec, ft: int, *,
             unified: bool, pack_survivors: bool, radix: int, layout: Layout,
             bm_dtype: str, mosaic: bool, vmem_budget: int) -> TilePlan:
    """A TilePlan at an arbitrary tile size under the same accounting as
    the analytic winner — candidate variants for the measuring pass."""
    model = unified_vmem_bytes if unified else split_vmem_bytes
    total, breakdown = model(trellis, plan_spec, ft,
                             pack_survivors=pack_survivors, radix=radix,
                             layout=layout, bm_dtype=bm_dtype, mosaic=mosaic)
    return TilePlan(int(ft), total, breakdown, vmem_budget,
                    "unified" if unified else "split", Layout(layout),
                    str(bm_dtype), bool(mosaic))


def _measure_candidates(trellis: Trellis, plan_spec: FrameSpec,
                        analytic: DecodePlan, *, layout, unified: bool,
                        pack_survivors: bool, radix: int, bm_dtype: str,
                        vmem_budget: int, eff_max, num_devices: int,
                        bf: int, ov: int, chunk_frames, top_k: int):
    """Top-k candidate plans for the timing pass: the analytic winner, the
    other layout's winner (layout='auto' only — the measurement exists to
    second-guess exactly this padding-model comparison), and the half/double
    tile variants of the winner (the footprint model is linear, but launch
    overhead vs pipelining is not). Deduped by cache_key; analytic order
    kept so ties resolve to the model's choice."""
    tiles = [analytic.tile]
    if layout == "auto":
        for lay in _layouts(unified):
            if lay is not analytic.tile.layout:
                tiles.append(plan_tiles(
                    trellis, plan_spec, pack_survivors=pack_survivors,
                    radix=radix, vmem_budget=vmem_budget, max_frames=eff_max,
                    unified=unified, layout=lay, bm_dtype=bm_dtype,
                    mosaic=True))
    ft0 = analytic.tile.frames_per_tile
    for ft in (ft0 // 2, ft0 * 2):
        if (CANDIDATE_TILES[0] <= ft <= MAX_FRAMES_PER_TILE
                and tile_ok(analytic.tile.layout, ft, eff_max)):
            tiles.append(_tile_at(
                trellis, plan_spec, ft, unified=unified,
                pack_survivors=pack_survivors, radix=radix,
                layout=analytic.tile.layout, bm_dtype=bm_dtype,
                mosaic=analytic.tile.mosaic, vmem_budget=vmem_budget))
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


def plan_decode(trellis: Trellis, spec: FrameSpec, *, unified: bool = True,
                pack_survivors: bool = True, radix: int = 4,
                bm_dtype: str = "float32", layout="auto",
                vmem_budget: int = DEFAULT_VMEM_BUDGET, num_devices: int = 1,
                chunk_frames: int | None = None,
                max_frames: int | None = None,
                frames_per_tile: int | None = None,
                block_frames: int | str = 1,
                overlap: int | None = None,
                measure: bool = False, tunedb: TuneDB | None = None,
                measure_top_k: int = 3, measure_reps: int = 2,
                measure_frames: int | None = None) -> DecodePlan:
    """Plan the whole decode: kernel, layout, tile, and chunk geometry.

    ``layout='auto'`` evaluates both layouts under mosaic (hardware-padded)
    accounting and keeps the one that fits more frames per tile at the
    given per-device ``vmem_budget`` (ties: fewer padded bytes) — the
    FT x S lane transpose wins only when tiles are small enough that
    frames cannot fill the 128 lanes. ``chunk_frames`` defaults to two
    tiles per device so the streaming front-end can double-buffer.
    ``frames_per_tile`` pins the tile instead of autotuning it (the serve
    layer passes a session's explicit knob through here so the plan — and
    its padding accounting — matches the kernel that actually launches).

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

    ``measure=True`` adds the on-device timing pass (ROADMAP item 3): the
    top-k analytic candidates (``_measure_candidates``) are timed with real
    launches (``measure_plan`` — compiled on accelerators, interpret on
    CPU) and the plan with the highest measured Mb/s wins. Timings are
    persisted to the disk-backed tune DB (kernels/tunedb.py; pass
    ``tunedb=`` to use a non-default instance) keyed by
    ``DecodePlan.fingerprint()`` x platform identity, so a plan is measured
    once per (hardware, code) pair and every later process — serve, stream,
    benchmarks — reuses the cached timing with zero re-measurement
    (``tunedb_hits`` tracer counters prove it).

    Every call runs under a ``plan_decode`` tracing span whose attributes
    carry the chosen plan (kernel, layout, tile, chunk geometry, block
    decomposition) and the predicted VMEM footprint vs budget — and, under
    ``measure=True``, the measured ms/Mb/s next to the predicted bytes plus
    how many candidates came from cache vs fresh measurement. The trace
    file records *why* the launch geometry is what it is.
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
                unified=unified, pack_survivors=pack_survivors, radix=radix,
                layout=_layouts(unified)[-1] if layout == "auto" else layout,
                bm_dtype=bm_dtype, vmem_budget=vmem_budget)
        elif layout == "auto":
            plans = [plan_tiles(trellis, plan_spec,
                                pack_survivors=pack_survivors,
                                radix=radix, vmem_budget=vmem_budget,
                                max_frames=eff_max, unified=unified,
                                layout=lay, bm_dtype=bm_dtype, mosaic=True)
                     for lay in _layouts(unified)]
            tile = max(plans, key=lambda p: (p.frames_per_tile, -p.vmem_bytes))
        else:
            tile = plan_tiles(trellis, plan_spec,
                              pack_survivors=pack_survivors,
                              radix=radix, vmem_budget=vmem_budget,
                              max_frames=eff_max, unified=unified,
                              layout=layout, bm_dtype=bm_dtype)
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
                    trellis, plan_spec, plan, layout=layout, unified=unified,
                    pack_survivors=pack_survivors, radix=radix,
                    bm_dtype=bm_dtype, vmem_budget=vmem_budget,
                    eff_max=eff_max, num_devices=num_devices, bf=bf, ov=ov,
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
        sp.set(kernel=tile.kernel, layout=Layout(tile.layout).value,
               frames_per_tile=tile.frames_per_tile,
               bm_dtype=str(tile.bm_dtype),
               chunk_frames=int(plan.chunk_frames),
               num_devices=int(num_devices), block_frames=int(bf),
               overlap=int(ov), vmem_bytes=tile.vmem_bytes,
               vmem_budget=tile.budget,
               fits=tile.vmem_bytes <= tile.budget,
               fingerprint=plan.fingerprint())
        return plan
