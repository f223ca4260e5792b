"""Streaming decode front-end: unbounded LLR streams, chunk by chunk.

``viterbi_decode_frames`` and ``make_decoder`` are single-shot: they want
the whole stream in memory, framed, before the first kernel launches. A
receiver does not work like that — samples arrive forever. This module
chunks an unbounded (n, beta) LLR stream into frame batches, keeps the
v1/v2 overlap context across chunk boundaries (so the chunked decode is
BIT-IDENTICAL to the single-shot framed decode of the same stream), and
double-buffers the per-chunk kernel dispatch:

  * chunk i is dispatched through JAX's async runtime and NOT waited on;
  * the host immediately frames chunk i+1 while the device decodes i;
  * results are materialized one chunk behind the dispatch front, so a
    ``block_until_ready`` never sits between two kernel launches.

The per-session state — the rolling v1/v2 overlap buffer, the
stream-global depuncture phase, and the chunk/flush window extraction —
lives in ``StreamContext``, separate from the dispatch machinery, so the
multi-tenant serve layer (repro.serve) can run one context per session
and batch the extracted windows of MANY sessions into a single kernel
launch. ``StreamDecoder`` is the single-session composition: one context
plus the double-buffered dispatch front.

Geometry: a chunk covers ``chunk_frames * spec.f`` kept stages; the decode
window around it is ``[start - v1, end + v2)``. The rolling buffer always
retains the v1 left-context samples of the NEXT chunk, the flush pads the
final partial chunk with zero LLRs (neutral, exactly like frame_llr's edge
padding), and the stream start is zero-padded the same way — hence the
bit-exact equivalence with ``framed_decode``.

Punctured rates are depunctured INSIDE ``push``: the context tracks the
stream-global pattern phase, so callers feed the raw punctured symbol
stream in arbitrary slices (the historical footgun — callers having to
depuncture the whole stream up front because alignment is stream-global —
is gone). Zero-LLR insertion is incremental and bit-identical to the
one-shot ``puncture.depuncture`` of the whole stream.

The chunk size and kernel configuration come from one
``kernels.autotune.plan_decode`` plan (the "full plan the front-end
executes"): tiles from the per-device VMEM budget, chunks as a multiple of
tiles x devices so a sharded decode (distributed/stream.py) keeps every
device busy every chunk. Window decoders are compiled once per
(trellis, spec, plan, nframes) in the process-global plan cache
(serve.plan_cache), so building a second StreamDecoder for the same
configuration — tenant churn — never re-traces.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import functools
import zlib

import numpy as np
import jax.numpy as jnp

from .pipeline import DecoderConfig
from .puncture import check_rate, pattern, unpunctured
from .sanitize import LLR_CLIP, sanitize_llr

__all__ = ["StreamContext", "StreamDecoder", "Window", "make_stream_decoder",
           "stream_decode", "STATE_VERSIONS"]

#: ``StreamContext.state_dict`` schema versions this build can write AND
#: read back. v1 stores the carry arrays as plain JSON lists (readable,
#: large); v2 stores them as base64 little-endian float32 bytes with a
#: CRC over the binary payload. Both round-trip bit-exactly.
STATE_VERSIONS = (1, 2)


def _enc_f32(arr: np.ndarray) -> str:
    """float32 array -> base64 of its little-endian bytes (bit-exact)."""
    return base64.b64encode(
        np.ascontiguousarray(arr, dtype="<f4").tobytes()).decode("ascii")


def _dec_f32(data: str, shape: tuple) -> np.ndarray:
    raw = base64.b64decode(data.encode("ascii"), validate=True)
    arr = np.frombuffer(raw, dtype="<f4").astype(np.float32)
    return arr.reshape(shape)


@dataclasses.dataclass(frozen=True)
class Window:
    """One extracted decode window: ``window`` spans
    ``[chunk_start - v1, chunk_end + v2)`` stages; decoding it yields
    ``nframes * f`` bits of which the first ``n_bits`` are real (the rest
    is flush padding)."""
    window: np.ndarray        # (v1 + nframes*f + v2, beta) float32
    nframes: int
    n_bits: int

    def frames(self, spec) -> np.ndarray:
        """The window's frames, (nframes, L, beta): frame m is rows
        ``[m*f, m*f + L)``, taken as a read-only strided view with no
        copy and no index array. Identical values to the jitted in-graph
        framing, so a batch built from these frames decodes
        bit-identically. The view aliases the context's buffer, which is
        never written in place (``StreamContext`` only rebuilds or
        re-slices it), so a queued window keeps its values."""
        if self.nframes == 1:
            return self.window[None]
        rows, cols = self.window.strides
        return np.lib.stride_tricks.as_strided(
            self.window, (self.nframes, spec.frame_len, self.window.shape[1]),
            (spec.f * rows, rows, cols), writeable=False)


@dataclasses.dataclass(frozen=True)
class _Period:
    """One period of a puncturing pattern as staging reads it: ``P``
    stages keep ``K`` symbols. From phase p, ``kept[p][j]`` symbols fill
    the next j stages (j = 0..P), ``stages[p][m]`` stages are complete
    with m < K symbols, and ``pos[p]`` holds the offsets of the K kept
    symbols, in stream order, within P stages of beta symbols."""
    P: int
    K: int
    kept: tuple
    stages: tuple
    pos: tuple


@functools.cache
def _period(rate: str) -> _Period:
    pat = pattern(rate)
    P = pat.shape[1]
    kept, stages, pos = [], [], []
    for p in range(P):
        mask = np.roll(pat, -p, axis=1).T            # (P, beta) from phase p
        cum = np.concatenate([[0], np.cumsum(mask.sum(axis=1))])
        kept.append(tuple(int(c) for c in cum))
        stages.append(tuple(int(np.searchsorted(cum[1:], m, side="right"))
                            for m in range(int(cum[-1]))))
        pos.append(np.flatnonzero(mask.reshape(-1)))
        pos[-1].flags.writeable = False              # shared by every caller
    return _Period(P, kept[0][-1], tuple(kept), tuple(stages), tuple(pos))


class StreamContext:
    """Per-session chunking state, extracted from StreamDecoder so the
    serve layer can batch windows across sessions.

    Holds the rolling overlap buffer (always retaining the v1 left
    context of the next chunk), the pushed/emitted stage counters, and —
    for punctured rates — the raw-symbol remainder plus the stream-global
    pattern phase. ``append`` absorbs raw input; ``take_windows`` yields
    every complete chunk window; ``flush_window`` zero-pads and yields the
    final partial chunk (or None if nothing is pending).

    The context is also the stream's numeric-robustness carry: every
    ``append`` validates the push shape and (``sanitize='zero'``, the
    default) scrubs NaN/Inf to neutral zero LLRs and clamps |llr| >
    ``llr_clip`` — bit-identical on clean inputs, with the cumulative
    scrub count in ``n_sanitized``/``numeric_stats()``. Per-stage
    path-metric renormalization inside each window's forward pass
    (DecoderConfig.renorm_every) plus this input clamp is what keeps an
    UNBOUNDED stream's metrics bounded in fp32/bf16 no matter how long
    the session lives. ``sanitize='raise'`` rejects poisoned pushes
    instead (the serve layer's strict-tenant policy); ``'off'`` skips the
    scan (the serve layer pre-sanitizes at its own boundary).
    """

    def __init__(self, spec, beta: int, chunk_frames: int,
                 rate: str | None = None, *, sanitize: str = "zero",
                 llr_clip: float = LLR_CLIP):
        assert chunk_frames > 0
        self.spec = spec
        self.beta = beta
        self.chunk_frames = chunk_frames
        self.rate = check_rate(rate, beta)       # None: unpunctured 1/beta
        self.sanitize = sanitize
        self.llr_clip = llr_clip
        self.reset()

    def reset(self):
        # the buffer holds [next_chunk_start - v1, ...); the stream start
        # gets the same zero left-context frame_llr would pad with
        self._buf = np.zeros((self.spec.v1, self.beta), np.float32)
        self._raw = np.zeros((0,), np.float32)  # punctured symbols pending
        self._phase = 0                         # stages depunctured so far
        self.n_in = 0                           # stages appended
        self.n_out = 0                          # bits covered by windows
        self.n_sanitized = 0                    # poisoned values scrubbed

    @functools.cached_property
    def punctured(self) -> bool:
        """Whether pushes are a raw punctured stream (depunctured here)
        rather than whole stages of beta soft symbols (read on every
        push; rate and beta never change)."""
        return self.rate != unpunctured(self.beta)

    def check_shape(self, llr: np.ndarray) -> None:
        """Reject structurally invalid pushes with a clear error (the raw
        reshape inside ``append`` would raise something cryptic)."""
        if llr.ndim > 2:
            raise ValueError(
                f"push must be flat or (m, beta); got shape {llr.shape}")
        if not self.punctured and llr.size % self.beta != 0:
            raise ValueError(
                f"rate-{self.rate} push of {llr.size} values is not a "
                f"multiple of beta={self.beta} soft symbols per stage")
        if llr.ndim == 2 and llr.shape[1] != self.beta:
            raise ValueError(
                f"2-D push must have beta={self.beta} columns; "
                f"got shape {llr.shape}")

    def numeric_stats(self) -> dict:
        """Cumulative numeric-hardening counters for this stream."""
        return {"stages_in": self.n_in, "bits_out": self.n_out,
                "sanitized_values": self.n_sanitized}

    # -- durable sessions: versioned carry-state serialization -------------
    def _geometry(self) -> dict:
        """The identity a saved state must match to be loadable: a state
        restored into a context of different frame geometry would decode
        different bits, so the mismatch is an error, never a best-effort
        load."""
        return {"f": self.spec.f, "v1": self.spec.v1, "v2": self.spec.v2,
                "beta": self.beta, "chunk_frames": self.chunk_frames,
                "rate": self.rate}

    def state_dict(self, version: int = 2) -> dict:
        """The session's complete carry state, JSON-ready and versioned.

        This is everything a fresh process needs to resume the stream
        BIT-IDENTICALLY: the rolling v1/v2 overlap buffer, the pending
        raw punctured tail, the stream-global depuncture phase, and the
        pushed/emitted/sanitized counters. The truncated-traceback
        insight (arXiv 1608.00066) is why this works and why it is
        small: frame m's decode depends only on the window
        ``[m*f - v1, (m+1)*f + v2)``, so a bounded carry window is all
        the state a session ever needs — ``load_state`` + replaying the
        not-yet-pushed input reproduces the uninterrupted stream's
        output exactly (tests/test_checkpoint.py gates the bit
        identity)."""
        if version not in STATE_VERSIONS:
            raise ValueError(f"unknown StreamContext state version "
                             f"{version}; this build writes {STATE_VERSIONS}")
        state = {"version": version, "geometry": self._geometry(),
                 "phase": int(self._phase), "n_in": int(self.n_in),
                 "n_out": int(self.n_out),
                 "n_sanitized": int(self.n_sanitized),
                 "buf_rows": int(self._buf.shape[0]),
                 "raw_len": int(self._raw.shape[0])}
        if version == 1:
            state["buf"] = [float(x) for x in self._buf.reshape(-1)]
            state["raw"] = [float(x) for x in self._raw]
        else:
            buf_b64 = _enc_f32(self._buf)
            raw_b64 = _enc_f32(self._raw)
            state["buf"] = buf_b64
            state["raw"] = raw_b64
            state["crc"] = zlib.crc32(
                (buf_b64 + "|" + raw_b64).encode("ascii"))
        return state

    def load_state(self, state: dict) -> None:
        """Restore a ``state_dict`` into this context (which must have
        the same geometry). Validates version, geometry, and — for v2
        states — the carry CRC before touching any field, so a corrupt
        or mismatched state never half-loads."""
        try:
            version = state["version"]
            geometry = state["geometry"]
        except (TypeError, KeyError) as e:
            raise ValueError(
                f"not a StreamContext state dict (missing {e})") from None
        if version not in STATE_VERSIONS:
            raise ValueError(
                f"unsupported StreamContext state version {version!r}; "
                f"this build reads {STATE_VERSIONS}")
        if geometry != self._geometry():
            raise ValueError(
                f"state geometry {geometry} does not match this context's "
                f"{self._geometry()}; restoring it would decode different "
                f"bits")
        buf_rows, raw_len = int(state["buf_rows"]), int(state["raw_len"])
        if version == 1:
            buf = np.asarray(state["buf"], np.float32).reshape(
                buf_rows, self.beta)
            raw = np.asarray(state["raw"], np.float32).reshape(raw_len)
        else:
            crc = zlib.crc32(
                (state["buf"] + "|" + state["raw"]).encode("ascii"))
            if crc != state.get("crc"):
                raise ValueError(
                    f"StreamContext state CRC mismatch (stored "
                    f"{state.get('crc')}, computed {crc}): the carry "
                    f"buffers are corrupt")
            try:
                buf = _dec_f32(state["buf"], (buf_rows, self.beta))
                raw = _dec_f32(state["raw"], (raw_len,))
            except ValueError as e:
                raise ValueError(
                    f"StreamContext carry buffers undecodable: {e}") \
                    from None
        # all fields validated — commit atomically
        self._buf = buf
        self._raw = raw
        self._phase = int(state["phase"])
        self.n_in = int(state["n_in"])
        self.n_out = int(state["n_out"])
        self.n_sanitized = int(state["n_sanitized"])

    # -- depuncturing (stream-global phase) -------------------------------
    def _complete_stages(self, r: int) -> int:
        """Complete stages ``r`` raw symbols fill from the current phase."""
        per = _period(self.rate)
        q, m = divmod(r, per.K)
        return q * per.P + per.stages[self._phase % per.P][m]

    def _stage(self, new: np.ndarray, final: bool) -> int:
        """Depuncture the raw carry plus ``new`` symbols into complete
        stages, written straight into a fresh ``_buf`` after its carried
        rows; returns the stages added.

        Bit-identical to one-shot ``puncture.depuncture`` of the whole
        stream: punctured positions become neutral zero LLRs. The stages
        come in three pieces: a head up to the next period boundary (a
        whole period when carried symbols start on the boundary, so the
        carry never reaches the body), a body of whole periods written as
        one (n, K) -> (n, P*beta) column assignment, and a tail of at most
        one period. ``final`` also emits a trailing stage the remainder
        only partly fills (missing kept symbols become zeros — an erased
        tail)."""
        per = _period(self.rate)
        P, K = per.P, per.K
        carry = self._raw
        c = carry.shape[0]
        p0 = self._phase % P
        s = self._complete_stages(c + new.shape[0])
        used = (s // P) * K + per.kept[p0][s % P]
        if final and used < c + new.shape[0]:
            s, used = s + 1, c + new.shape[0]         # partial last stage
        if s == 0:                                    # under one stage
            self._raw = np.concatenate([carry, new])
            return 0
        rows = self._buf.shape[0]
        buf = np.zeros((rows + s, self.beta), np.float32)
        buf[:rows] = self._buf
        out = buf[rows:]
        h = min(s, (-p0) % P or (P if c else 0))
        m = min(per.kept[p0][h], used)                # head symbols, >= c
        if h:
            out[:h].reshape(-1)[per.pos[p0][:m]] = np.concatenate(
                [carry, new[:m - c]])
        a = m - c                                     # new symbols consumed
        n = min((s - h) // P, (used - m) // K)
        if n:
            out[h:h + n * P].reshape(n, P * self.beta)[:, per.pos[0]] = \
                new[a:a + n * K].reshape(n, K)
            a += n * K
        k = used - c - a
        if k:
            out[h + n * P:].reshape(-1)[per.pos[0][:k]] = new[a:a + k]
        self._raw = new[used - c:].copy()             # may alias the caller
        self._buf = buf
        self._phase += s
        self.n_in += s
        return s

    # -- input / window extraction ----------------------------------------
    def append(self, llr) -> int:
        """Absorb raw input; returns the number of stages added.

        unpunctured (1/beta): (m, beta) or flat (m*beta,) soft symbols.
        punctured: the raw punctured symbol stream, flat, any slice size —
        the pattern alignment is tracked here, stream-globally."""
        llr = np.asarray(llr, np.float32)
        self.check_shape(llr)
        if self.sanitize != "off":
            llr, n_bad = sanitize_llr(llr, self.llr_clip, self.sanitize)
            self.n_sanitized += n_bad
        if self.punctured:
            return self._stage(llr.reshape(-1), final=False)
        staged = llr.reshape(-1, self.beta)
        if staged.size:
            self._buf = np.concatenate([self._buf, staged])
            self.n_in += staged.shape[0]
        return staged.shape[0]

    def incoming_stages(self, llr) -> int:
        """Stages ``append(llr)`` would add — exact, including the
        punctured-rate phase and raw remainder (the serve layer's
        backpressure check runs BEFORE absorbing anything)."""
        llr = np.asarray(llr)
        if not self.punctured:
            return llr.size // self.beta
        return self._complete_stages(self._raw.shape[0] + llr.size)

    def projected_windows(self, add_stages: int) -> int:
        """Complete chunk windows extractable once ``add_stages`` more
        stages arrive (counting what is already buffered)."""
        buf_after = self._buf.shape[0] + add_stages
        return max(0, (buf_after - self.spec.v1 - self.spec.v2)
                   // (self.chunk_frames * self.spec.f))

    def take_windows(self) -> list[Window]:
        """Every complete chunk window currently extractable."""
        spec, C = self.spec, self.chunk_frames
        ck = C * spec.f                          # kept stages per chunk
        need = spec.v1 + ck + spec.v2            # full decode window
        out = []
        while self._buf.shape[0] >= need:
            out.append(Window(self._buf[:need], C, ck))
            self._buf = self._buf[ck:]           # keep next chunk's v1 lead
            self.n_out += ck
        return out

    def _stage_raw_tail(self):
        """Flush-time prelude: convert any leftover raw punctured symbols
        (including a partly-filled final stage) into buffered stages."""
        if self.punctured and self._raw.size:
            self._stage(np.zeros((0,), np.float32), final=True)

    def flush_window(self) -> Window | None:
        """The zero-padded final partial chunk (frame_llr's edge padding)
        as ONE window of ceil(tail/f) frames — possibly more than
        ``chunk_frames`` when the last chunk was only missing its v2
        right context. None when every pushed stage is already covered.
        Resets nothing — call ``reset`` to reuse the context."""
        self._stage_raw_tail()
        spec = self.spec
        tail = self.n_in - self.n_out            # stages not yet windowed
        if tail <= 0:
            return None
        nframes = -(-tail // spec.f)
        need = spec.v1 + nframes * spec.f + spec.v2
        window = self._buf
        if window.shape[0] < need:
            pad = np.zeros((need - window.shape[0], self.beta), np.float32)
            window = np.concatenate([window, pad])
        self.n_out += tail
        return Window(window[:need], nframes, tail)

    def flush_chunks(self) -> list[Window]:
        """Flush for the serve layer: the tail as a SEQUENCE of full
        ``chunk_frames`` windows (zero-padded at the stream end), each
        carrying its share of ``n_bits`` — so a bucket keeps its one
        window geometry no matter how long the tail is (it can exceed one
        chunk by up to v2-1 stages of missing right context). The windows
        decode bit-identically to flush_window's single window: frame m's
        decode region depends only on the zero-extended stream."""
        self._stage_raw_tail()
        spec, C = self.spec, self.chunk_frames
        tail = self.n_in - self.n_out
        if tail <= 0:
            return []
        ck = C * spec.f
        nwin = -(-tail // ck)
        need = spec.v1 + nwin * ck + spec.v2
        if self._buf.shape[0] < need:
            pad = np.zeros((need - self._buf.shape[0], self.beta),
                           np.float32)
            self._buf = np.concatenate([self._buf, pad])
        out = []
        for _ in range(nwin):
            n_bits = min(ck, tail)
            out.append(Window(self._buf[:spec.v1 + ck + spec.v2], C, n_bits))
            self._buf = self._buf[ck:]
            tail -= n_bits
            self.n_out += n_bits
        return out


class StreamDecoder:
    """Incremental decoder: ``push`` LLR samples, collect decoded bits.

    push() returns the bits whose chunks have *completed* (possibly an
    empty array — results trail the dispatch front by ``depth`` chunks);
    flush() decodes the zero-padded tail and drains everything pending.
    The instance is reusable after flush(). Feed (m, beta) soft symbols,
    or — for punctured rates — the raw punctured symbol stream (the
    context depunctures in-stream; see StreamContext).
    """

    def __init__(self, cfg: DecoderConfig, chunk_frames: int, *,
                 depth: int = 1, mesh=None, decode_frames=None, cache=None,
                 faults=None, sanitize: str = "zero", trace=None):
        assert chunk_frames > 0 and depth >= 0
        self.cfg = cfg
        self.spec = cfg.spec
        self.beta = cfg.trellis.beta
        self.chunk_frames = chunk_frames
        self.depth = depth                      # chunks left in flight
        self.mesh = mesh
        self._decode_frames = decode_frames     # explicit override only
        self._local_fns = {}                    # override path: per-instance
        if cache is None:
            from ..serve.plan_cache import PLAN_CACHE as cache
        self._cache = cache
        # tracing hook (repro.obs): chunk dispatches become sync spans and
        # each in-flight chunk an ASYNC span spanning dispatch ->
        # materialize, so the double-buffer overlap is visible as
        # concurrent spans in the exported trace. None resolves to the
        # process-global tracer (a pay-nothing no-op unless enabled).
        if trace is None:
            from ..obs.tracer import get_tracer
            trace = get_tracer()
        self.trace = trace
        # fault-injection hook (repro.testing.faults) — None in production.
        # The single-stream front-end has no retry machinery: an injected
        # launch fault propagates to the caller (the multi-tenant server
        # is the layer that retries/degrades).
        self._faults = faults
        self._ctx = StreamContext(cfg.spec, self.beta, chunk_frames,
                                  cfg.rate, sanitize=sanitize)
        self._inflight = collections.deque()    # (device_array, n_bits)

    def _window_decoder(self, nframes: int):
        """Jitted window -> bits for a chunk of ``nframes`` frames. Comes
        from the process-global plan cache — every StreamDecoder (and
        serve bucket) of the same (trellis, spec, plan, nframes) shares
        ONE compilation; flush tails compile once per distinct length. An
        explicit decode_frames override has no cacheable identity, so it
        is memoized per instance instead (one compile per length, as
        before the cache existed)."""
        if self._decode_frames is not None:
            fn = self._local_fns.get(nframes)
            if fn is None:
                from ..serve.plan_cache import build_window_fn
                fn = build_window_fn(self.cfg.spec, self._decode_frames,
                                     nframes)
                self._local_fns[nframes] = fn
            return fn
        return self._cache.window_decoder(self.cfg, nframes, mesh=self.mesh)

    def _dispatch(self, w: Window):
        with self.trace.span("dispatch", nframes=w.nframes,
                             n_bits=w.n_bits):
            if self._faults is not None:
                self._faults.launch("stream")
            bits = self._window_decoder(w.nframes)(jnp.asarray(w.window))
        # async span: dispatch -> materialize; overlapping chunk spans ARE
        # the double buffering, rendered as overlap by the Chrome exporter
        self._inflight.append(
            (bits, w.n_bits,
             self.trace.begin("chunk", nframes=w.nframes, n_bits=w.n_bits)))

    def _drain(self, leave: int) -> list[np.ndarray]:
        out = []
        while len(self._inflight) > leave:
            bits, n_bits, chunk_span = self._inflight.popleft()
            out.append(np.asarray(bits)[:n_bits])   # blocks on OLDEST only
            chunk_span.end()
        return out

    def push(self, llr) -> np.ndarray:
        """Feed soft symbols; returns the decoded bits of every chunk that
        has completed so far. The context validates the push shape and
        sanitizes NaN/Inf/out-of-range values (see StreamContext)."""
        with self.trace.span("push"):
            if self._faults is not None:
                llr = self._faults.corrupt(llr)
            self._ctx.append(llr)
            out = []
            for w in self._ctx.take_windows():
                self._dispatch(w)
                out.extend(self._drain(self.depth))
        return (np.concatenate(out) if out
                else np.zeros((0,), np.int32))

    def flush(self) -> np.ndarray:
        """Decode the zero-padded tail, drain all in-flight chunks, and
        reset for the next stream. Returns the remaining decoded bits."""
        with self.trace.span("flush"):
            w = self._ctx.flush_window()
            if w is not None:
                self._dispatch(w)
            out = self._drain(0)
            self._ctx.reset()
        return (np.concatenate(out) if out
                else np.zeros((0,), np.int32))

    def numeric_stats(self) -> dict:
        """The context's cumulative numeric-hardening counters."""
        return self._ctx.numeric_stats()


def make_stream_decoder(cfg: DecoderConfig, *, chunk_frames: int | None = None,
                        mesh=None, depth: int = 1, cache=None, faults=None,
                        trace=None) -> StreamDecoder:
    """Build a StreamDecoder for ``cfg``.

    chunk_frames: frames per chunk; default comes from
      kernels.autotune.plan_decode — two kernel tiles per device, so the
      dispatch pipeline and (if ``mesh`` is given) every device stay busy.
    mesh: optional jax Mesh with a 'frames' axis; chunks are then decoded
      with the sharded frame decoder (distributed/stream.py), frames tiled
      across the mesh devices.
    depth: chunks allowed in flight behind the dispatch front (1 = classic
      double buffering; 0 = synchronous, for debugging).
    cache: plan cache override (default: the process-global PLAN_CACHE).
    faults: optional repro.testing.faults.FaultInjector (test harness).
    trace: optional repro.obs.Tracer (None = the process-global tracer,
      a no-op unless ``repro.obs.set_tracer`` enabled one).
    """
    num_devices = int(mesh.devices.size) if mesh is not None else 1
    if chunk_frames is None:
        from ..kernels.autotune import plan_decode
        plan = plan_decode(
            cfg.trellis, cfg.spec, unified=cfg.backend != "kernel_split",
            pack_survivors=cfg.pack_survivors, radix=cfg.radix,
            bm_dtype=cfg.bm_dtype, layout=cfg.layout,
            num_devices=num_devices,
            block_frames=cfg.block_frames, overlap=cfg.overlap)
        chunk_frames = plan.chunk_frames
    return StreamDecoder(cfg, chunk_frames, depth=depth, mesh=mesh,
                         cache=cache, faults=faults, trace=trace)


def stream_decode(cfg: DecoderConfig, llr, n: int | None = None, *,
                  chunk_frames: int | None = None, mesh=None,
                  push_size: int | None = None) -> np.ndarray:
    """Convenience one-call wrapper: stream ``llr`` through a
    StreamDecoder in ``push_size``-sized pushes and return the first n
    bits — bit-identical to ``make_decoder(cfg)(llr, n)``. Like
    make_decoder, a punctured-rate cfg takes the raw punctured symbol
    stream (and needs ``n``); it is depunctured in-stream by the decoder's
    StreamContext (push_size then counts raw symbols)."""
    llr = np.asarray(llr, np.float32)
    if cfg.punctured:
        if n is None:
            raise ValueError("n is required for punctured rates")
        llr = llr.reshape(-1)                    # raw punctured symbols
    else:
        llr = llr.reshape(-1, cfg.trellis.beta)
    if n is None:
        n = llr.shape[0]
    dec = make_stream_decoder(cfg, chunk_frames=chunk_frames, mesh=mesh)
    if push_size is None:
        push_size = max(1, dec.chunk_frames) * cfg.spec.f
    parts = [dec.push(llr[i:i + push_size])
             for i in range(0, llr.shape[0], push_size)]
    parts.append(dec.flush())
    return np.concatenate(parts)[:n]
