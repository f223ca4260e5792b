"""The retire's copy back starts at dispatch (serve/server.py
``_inflight``): every launch starts its device-to-host copy as it goes in
flight, counted by the tracer's ``d2h_prefetch`` counter; the bits served
are unchanged; a copy that cannot start is no launch fault, and an output
that cannot be read at all degrades exactly once."""
import dataclasses

import numpy as np
import pytest

from conftest import noisy_llr
from repro.core import DecoderConfig, FrameSpec, make_decoder
from repro.core.puncture import pattern, puncture
from repro.obs import Tracer
from repro.serve import DecodeServer, PlanCache

SPEC = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
SPEC34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)


def _llr(cfg, n, rng):
    """Noisy received stream for ``cfg``: (n, 2) soft symbols, or the
    flat punctured stream for a punctured rate."""
    llr = noisy_llr(rng.integers(0, 2, n), cfg.trellis, 3.0, rng)
    return np.asarray(puncture(llr, cfg.rate)) if cfg.punctured else llr


def _serve(srv, cfgs, streams, n, push):
    """Push every session's stream in ``push``-stage pieces, stepping
    after each round; close each session and return its bits."""
    sids = [srv.open_session(cfg, chunk_frames=2) for cfg in cfgs]
    # symbols of each stream before each stage 0..n
    starts = [np.concatenate([[0], np.cumsum(
        np.resize(pattern(cfg.rate).sum(0), n))]) if cfg.punctured
        else np.arange(n + 1) for cfg in cfgs]
    outs = {sid: [] for sid in sids}
    for lo in range(0, n, push):
        hi = min(n, lo + push)
        for sid, llr, at in zip(sids, streams, starts):
            srv.push(sid, llr[at[lo]:at[hi]])
        srv.step()
        for sid in sids:
            outs[sid].append(srv.poll(sid))
    for sid in sids:
        outs[sid].append(srv.close_session(sid))
    return [np.concatenate(outs[sid])[:n] for sid in sids]


def _reference(cfg, llr, n):
    """The framed reference decode of the whole stream."""
    ref = dataclasses.replace(cfg, backend="reference")
    return np.asarray(make_decoder(ref)(llr, n))


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_every_launch_starts_its_copy_back(rng, depth):
    """Under a recording tracer the ``d2h_prefetch`` counter equals the
    launches retired, at every pipeline depth, the close_session flush
    of a partial tail included."""
    cfg = DecoderConfig(spec=SPEC)
    n = 9 * SPEC.f + 17                      # a partial tail to flush
    llr = _llr(cfg, n, rng)
    tr = Tracer()
    srv = DecodeServer(slots=2, depth=depth, cache=PlanCache(), trace=tr)
    sid = srv.open_session(cfg, chunk_frames=2)
    srv.push(sid, llr[:5 * SPEC.f])
    while srv.step():
        pass
    launched = tr.counters()["d2h_prefetch"]
    assert launched >= 1
    srv.push(sid, llr[5 * SPEC.f:])
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    tot = srv.metrics.totals()
    assert tr.counters()["d2h_prefetch"] == tot["launches"] > launched
    assert sum(r.name == "inflight" for r in tr.spans()) == tot["launches"]
    assert np.array_equal(got, _reference(cfg, llr, n))


def test_bits_served_equal_the_framed_reference(rng):
    """Over many launches, one punctured (rate 3/4) and one unpunctured
    session in their buckets get exactly the reference's bits."""
    cfgs = [DecoderConfig(spec=SPEC34, rate="3/4"),
            DecoderConfig(spec=SPEC)]
    n = 40 * 63
    streams = [_llr(cfg, n, rng) for cfg in cfgs]
    tr = Tracer()
    srv = DecodeServer(slots=2, cache=PlanCache(), trace=tr)
    got = _serve(srv, cfgs, streams, n, push=3 * 63)
    for cfg, llr, bits in zip(cfgs, streams, got):
        assert np.array_equal(bits, _reference(cfg, llr, n))
    tot = srv.metrics.totals()
    assert tot["launches"] >= 20
    assert tr.counters()["d2h_prefetch"] == tot["launches"]
    assert tot["degraded"] == 0 and tot["launch_errors"] == 0


class _Unreadable:
    """A launch output whose copy back cannot start and whose
    materialization raises: the device was lost after the dispatch."""

    def copy_to_host_async(self):
        raise RuntimeError("copy back cannot start")

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device lost before the copy back")


class _NoPrefetch:
    """A healthy launch output whose copy back cannot be started early."""

    def __init__(self, out):
        self.out = out

    def copy_to_host_async(self):
        raise RuntimeError("copy back cannot start")

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.out, dtype=dtype)


class _FirstLaunchBroken(PlanCache):
    """The first launch's output is wrapped by ``wrap``; the rest, the
    fallback's included, are the plan cache's own."""

    def __init__(self, wrap):
        super().__init__()
        self.wrap = wrap

    def batch_decoder(self, cfg, nframes, **kw):
        fn = super().batch_decoder(cfg, nframes, **kw)

        def run(frames):
            out = fn(frames)
            if self.wrap is None:
                return out
            wrap, self.wrap = self.wrap, None
            return wrap(out)

        return run


@pytest.mark.parametrize("readable", [False, True])
def test_copy_that_cannot_start_is_no_launch_fault(rng, readable):
    """A copy back that fails to start neither retries nor counts a
    fault. If the output cannot be read either, ``_retire`` re-decodes
    the batch by the reference fallback: one launch error, one degraded
    launch, no retry, no breaker trip, and the bits stay exact."""
    cfg = DecoderConfig(spec=SPEC)
    n = 8 * SPEC.f
    llr = _llr(cfg, n, rng)
    wrap = _NoPrefetch if readable else (lambda out: _Unreadable())
    tr = Tracer()
    srv = DecodeServer(slots=2, cache=_FirstLaunchBroken(wrap), trace=tr,
                       backoff_s=0.0)
    sid = srv.open_session(cfg, chunk_frames=2)
    srv.push(sid, llr)
    while srv.step():
        pass
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    assert np.array_equal(got, _reference(cfg, llr, n))
    tot = srv.metrics.totals()
    faults = 0 if readable else 1
    assert tot["launch_errors"] == faults and tot["degraded"] == faults
    assert tot["retries"] == 0 and tot["breaker_trips"] == 0
    assert tot["timeouts"] == 0
    assert tr.counters()["d2h_prefetch"] == tot["launches"] - 1
