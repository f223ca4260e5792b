"""Compiled-decoder / plan cache shared by the serve, stream, and
pipeline layers.

Every layer that decodes frames ends up building the same two artifacts:
an (unjitted) ``decode_frames`` closure dispatching one backend
configuration, and a jitted wrapper specialized to a fixed frame count
(a stream chunk window, or a serve bucket's batch). Before this cache,
each ``StreamDecoder`` / ``make_decoder`` call built fresh closures — and
because JAX's jit cache is keyed by function *identity*, every new
closure meant a full re-trace and re-compile of an identical program.
Under tenant churn (sessions opening and closing all day) that is a
compile per session.

``PlanCache`` is the process-global registry fixing that. Entries are
keyed by the semantic identity of the compiled program::

    (trellis, spec, DecodePlan, nframes)

materialized here as ``(kind, cfg, nframes, mesh)`` — a ``DecoderConfig``
*is* (trellis, spec, plan knobs), its trellis hashes by canonical
identity (``make_trellis`` is lru_cached), and the kernel-knob subset of
the key is exactly ``kernels.autotune.DecodePlan.cache_key()``. Three
entry kinds:

  * ``frames``  — the backend-dispatch closure (pipeline layer);
  * ``window``  — jitted chunk-window -> bits (stream layer);
  * ``batch``   — jitted (nframes, L, beta) -> (nframes, f) bits
                  (serve layer: one bucket launch).

``stats()`` reports hits / misses and — the number that matters for the
serve acceptance criterion — ``traces``: how many times XLA actually
traced a cached program. One trace per distinct (trellis, spec, plan,
nframes) bucket, no matter how many sessions come and go.
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp

from ..core.framed import flatten_bits
from ..core.pipeline import DecoderConfig, _build_frame_decoder
from ..obs.tracer import get_tracer

__all__ = ["PlanCache", "PLAN_CACHE", "build_window_fn", "plan_attrs"]


def plan_attrs(cfg: DecoderConfig, nframes: int | None = None,
               mesh=None) -> dict:
    """What a plan of ``cfg`` chose, as span and snapshot attributes: the
    trellis's ``states`` and ``beta`` and, for a kernel backend launching
    ``nframes`` frames (over ``mesh``'s devices), the kernel tile's
    ``frames_per_tile`` and its planned VMEM bytes a grid step."""
    from ..kernels.autotune import launch_tile
    from ..kernels.block import resolve_block
    tr = cfg.trellis
    attrs = {"states": tr.num_states, "beta": tr.beta}
    if cfg.backend == "reference" or nframes is None:
        return attrs
    bf, ov = resolve_block(tr, cfg.spec, cfg.block_frames, cfg.overlap)
    ndev = int(mesh.devices.size) if mesh is not None else 1
    tile = launch_tile(
        tr, cfg.spec.blocked(bf, ov) if bf > 1 else cfg.spec,
        -(-int(nframes) // ndev) * bf, frames_per_tile=cfg.frames_per_tile,
        pack_survivors=cfg.pack_survivors)
    attrs.update(frames_per_tile=tile.frames_per_tile,
                 vmem_bytes=tile.vmem_bytes)
    return attrs


def build_window_fn(spec, decode_frames, nframes: int, trace_hook=None):
    """Jitted window -> bits for a chunk of ``nframes`` frames: frame the
    (v1 + nframes*f + v2, beta) window in-graph, decode, flatten.
    ``trace_hook`` (if given) runs at trace time only — the cache uses it
    to count real compilations."""
    L, f = spec.frame_len, spec.f

    @jax.jit
    def run(window):
        if trace_hook is not None:
            trace_hook()
        starts = jnp.arange(nframes) * f
        idx = starts[:, None] + jnp.arange(L)[None, :]
        frames = window[idx]                    # (nframes, L, beta)
        return flatten_bits(decode_frames(frames))

    return run


class PlanCache:
    """Thread-safe registry of compiled decode programs.

    The default instance is the module-global ``PLAN_CACHE``; tests and
    servers that want isolated accounting pass their own.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._fns: dict = {}
        self.hits = 0
        self.misses = 0
        self.traces = 0
        self.build_ms = 0.0

    # -- bookkeeping ------------------------------------------------------
    def _get(self, key, build, plan: tuple, refresh: bool = False):
        """Cached build. ``refresh=True`` drops any existing entry first —
        the fault-injection harness uses it to force the cold path (an
        evicted / never-compiled plan) on a live server. Misses time the
        build under a ``plan_build`` span, which a recording tracer gets
        with ``plan_attrs(*plan)``; ``stats()`` counts hits, misses and
        traces."""
        with self._lock:
            if refresh:
                self._fns.pop(key, None)
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
        t0 = time.perf_counter()
        tracer = get_tracer()
        attrs = plan_attrs(*plan) if tracer.enabled else {}
        with tracer.span("plan_build", kind=str(key[0]), **attrs):
            fn = build()                        # build outside the lock
        dt_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self.build_ms += dt_ms
            return self._fns.setdefault(key, fn)

    def _mark_trace(self):
        with self._lock:
            self.traces += 1

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._fns), "hits": self.hits,
                    "misses": self.misses, "traces": self.traces,
                    "build_ms": round(self.build_ms, 3)}

    def clear(self):
        with self._lock:
            self._fns.clear()
            self.hits = self.misses = self.traces = 0
            self.build_ms = 0.0

    # -- entries ----------------------------------------------------------
    def frame_decoder(self, cfg: DecoderConfig, mesh=None):
        """The backend-dispatch ``decode_frames`` closure for ``cfg`` —
        ONE closure per (cfg, mesh), so every jit built on top of it
        shares downstream compilation cache lines. With ``mesh``, the
        frame axis is sharded across the mesh devices
        (distributed/stream.py)."""
        if mesh is None:
            return self._get(("frames", cfg),
                             lambda: _build_frame_decoder(cfg), (cfg,))

        def build():
            from ..distributed.stream import make_sharded_frame_decoder
            return make_sharded_frame_decoder(cfg, mesh)

        return self._get(("frames", cfg, mesh), build, (cfg, None, mesh))

    def window_decoder(self, cfg: DecoderConfig, nframes: int, *, mesh=None):
        """Jitted chunk-window decoder (stream layer). Callers with a
        custom decode_frames closure must memoize their own
        ``build_window_fn`` result — an anonymous closure has no stable
        identity to key a shared registry on."""
        key = ("window", cfg, int(nframes), mesh)
        return self._get(key, lambda: build_window_fn(
            cfg.spec, self.frame_decoder(cfg, mesh), int(nframes),
            self._mark_trace), key[1:])

    def batch_decoder(self, cfg: DecoderConfig, nframes: int, *, mesh=None,
                      refresh: bool = False):
        """Jitted (nframes, L, beta) frames -> (nframes, f) bits — the
        serve layer's one-launch-per-bucket entry point. ``nframes`` is
        the bucket's fixed batch (slots x chunk_frames), so each bucket
        compiles exactly once. ``refresh`` forces a rebuild (fault
        injection only — exercises the cold-cache path)."""
        key = ("batch", cfg, int(nframes), mesh)

        def build():
            decode_frames = self.frame_decoder(cfg, mesh)
            mark = self._mark_trace

            @jax.jit
            def run(frames):
                mark()
                return decode_frames(frames)

            return run

        return self._get(key, build, key[1:], refresh=refresh)


#: Process-global cache: tenant churn anywhere in the process never
#: re-compiles a plan it has seen before.
PLAN_CACHE = PlanCache()
