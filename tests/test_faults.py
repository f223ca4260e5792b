"""Fault-tolerance suite: sanitization, fault injection, retry/degrade,
quarantine, renormalization, and the hardened error surfaces.

The adversarial-input property tests pin the contract "sanitize OR raise,
never silent garbage": a poisoned buffer pushed through any decode entry
point either comes out exactly as if the caller had sanitized it first,
or raises a structured error — and a server that saw it keeps serving
its healthy tenants bit-identically.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from conftest import noisy_llr
from repro.core import (DecoderConfig, FrameSpec, LLR_CLIP, STD_K7,
                        make_decoder, sanitize_llr, stream_decode)
from repro.core.stream import make_stream_decoder
from repro.obs import Tracer
from repro.serve import (Backpressure, DecodeServer, PlanCache,
                         PoisonedInput, ServeError, ServerFull,
                         SessionQuarantined)
from repro.testing import FaultInjector, FaultSpec, InjectedKernelError

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")

SPEC = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)


def _poison(llr, rng, mode, frac=0.2):
    """A copy of ``llr`` with a ``frac`` fraction of entries poisoned."""
    out = np.array(llr, np.float32)
    flat = out.reshape(-1)
    k = max(1, int(frac * flat.size))
    idx = rng.choice(flat.size, size=k, replace=False)
    val = {"nan": np.nan, "inf": np.inf, "huge": 1e30}[mode]
    flat[idx] = val
    if mode != "nan":
        flat[idx[1::2]] *= -1.0
    return out


# ---------------------------------------------------------------- sanitize
def test_sanitize_llr_policies(rng):
    llr = rng.standard_normal((64, 2)).astype(np.float32)
    # clean input: returned UNTOUCHED (the bit-identity fast path)
    out, n = sanitize_llr(llr)
    assert n == 0 and out is llr
    bad = llr.copy()
    bad[0, 0], bad[1, 1], bad[2, 0], bad[3, 1] = (np.nan, np.inf,
                                                  -np.inf, 2e6)
    out, n = sanitize_llr(bad)
    assert n == 4 and bad[3, 1] == 2e6          # input not mutated
    assert out[0, 0] == 0.0 and out[1, 1] == 0.0 and out[2, 0] == 0.0
    assert out[3, 1] == LLR_CLIP
    assert np.array_equal(out[4:], bad[4:])
    with pytest.raises(ValueError, match="4 non-finite"):
        sanitize_llr(bad, policy="raise")
    out, n = sanitize_llr(bad, policy="off")
    assert n == 0 and out is bad


def _sanitize_six_pass(llr, clip=LLR_CLIP, policy="zero"):
    """The exact six-pass scan (isfinite, abs, compare, invert, or, sum)
    with no clean check in front: the reference sanitize_llr's answers
    are held to, bit for bit."""
    arr = np.asarray(llr, np.float32)
    finite = np.isfinite(arr)
    bad = ~finite | (np.abs(arr) > clip)
    n_bad = int(bad.sum())
    if n_bad == 0:
        return arr, 0
    if policy == "raise":
        raise ValueError(
            f"{n_bad} non-finite or out-of-range (|llr| > {clip:g}) "
            f"values in a push of {arr.size}")
    out = np.where(finite, np.clip(arr, -clip, clip), np.float32(0.0))
    return out.astype(np.float32, copy=False), n_bad


def _assert_matches_six_pass(llr, clip=LLR_CLIP):
    """sanitize_llr == the six-pass scan under 'zero' and 'raise': the
    same n_bad, output bits and refusal; a clean float32 push comes back
    as the very input object."""
    for policy in ("zero", "raise"):
        try:
            want, want_err = _sanitize_six_pass(llr, clip, policy), None
        except ValueError as e:
            want, want_err = None, str(e)
        try:
            got, got_err = sanitize_llr(llr, clip, policy), None
        except ValueError as e:
            got, got_err = None, str(e)
        assert got_err == want_err
        if want is None:
            continue
        (out, n_bad), (ref, ref_bad) = got, want
        assert n_bad == ref_bad
        assert out.dtype == np.float32 and out.shape == ref.shape
        assert out.tobytes() == ref.tobytes()
        if n_bad == 0 and isinstance(llr, np.ndarray) \
                and llr.dtype == np.float32:
            assert out is llr


def _at(value, where, n=64):
    out = np.linspace(-3.0, 3.0, n, dtype=np.float32)
    out[{"first": 0, "middle": n // 2, "last": n - 1}[where]] = value
    return out


_F32_TENTH = np.float32(0.1)
_SANITIZE_CASES = {
    **{f"{name}_{where}": (lambda v=v, w=where: (_at(v, w), LLR_CLIP))
       for name, v in (("nan", np.nan), ("pinf", np.inf),
                       ("ninf", -np.inf))
       for where in ("first", "middle", "last")},
    "at_clip": lambda: (np.array([LLR_CLIP, -LLR_CLIP, 0.5],
                                 np.float32), LLR_CLIP),
    "above_clip": lambda: (np.array(
        [0.5, np.nextafter(np.float32(LLR_CLIP), np.inf)], np.float32),
        LLR_CLIP),
    "below_neg_clip": lambda: (np.array(
        [np.nextafter(np.float32(-LLR_CLIP), -np.inf), 0.5], np.float32),
        LLR_CLIP),
    "neg_zero": lambda: (np.array([-0.0, 0.0, -0.0], np.float32), LLR_CLIP),
    "empty": lambda: (np.zeros((0, 2), np.float32), LLR_CLIP),
    "m2_clean": lambda: (np.arange(-8, 8, dtype=np.float32).reshape(8, 2),
                         LLR_CLIP),
    "m2_poisoned": lambda: (np.array([[1.0, np.nan], [2e6, -3.0]],
                                     np.float32), LLR_CLIP),
    "m3_clean": lambda: (np.linspace(-9, 9, 12, dtype=np.float32)
                         .reshape(4, 3), LLR_CLIP),
    "m3_poisoned": lambda: (np.array([[1.0, 2.0, -np.inf],
                                      [-2e7, 0.0, 4.0]], np.float32),
                            LLR_CLIP),
    "float64_clean": lambda: (np.array([1e6 + 0.01, -1.5, 0.0]), LLR_CLIP),
    "float64_poisoned": lambda: (np.array([1e6 + 1.0, np.nan, -1.5]),
                                 LLR_CLIP),
    # 0.1 is not a float32: values on either side of float32(0.1), with
    # the threshold as a Python float (the scan compares in float32) and
    # as a numpy float64 (the scan compares in float64)
    **{f"tenth_{kind}_{side}": (lambda c=c, d=d: (np.array(
        [0.0, np.nextafter(_F32_TENTH, d * np.inf) if d else _F32_TENTH,
         -0.05], np.float32), c))
       for kind, c in (("py", 0.1), ("f64", np.float64(0.1)))
       for side, d in (("below", -1), ("at", 0), ("above", 1))},
    "clip_inf_with_inf": lambda: (np.array([1.0, np.inf], np.float32),
                                  np.inf),
    "clip_inf_finite": lambda: (np.array([3e38, -3e38], np.float32),
                                np.inf),
}


@pytest.mark.parametrize("case", sorted(_SANITIZE_CASES))
def test_sanitize_llr_matches_six_pass_scan(case):
    """The clean check (one max, one min) engages only where the old scan
    finds nothing: every answer is the scan's, bit for bit."""
    llr, clip = _SANITIZE_CASES[case]()
    _assert_matches_six_pass(llr, clip)


@given(st.integers(0, 2**32 - 1), st.sampled_from(["nan", "inf", "huge"]))
def test_sanitize_llr_matches_six_pass_scan_random(seed, mode):
    """Random positions poisoned in a noisy (m, 2) push, and the clean
    push itself: sanitize_llr answers as the six-pass scan does."""
    rng = np.random.default_rng(seed)
    llr = noisy_llr(rng.integers(0, 2, 256), STD_K7, 3.0, rng)
    _assert_matches_six_pass(llr)
    _assert_matches_six_pass(_poison(llr, rng, mode,
                                     frac=float(rng.uniform(0.001, 0.5))))


@given(st.integers(0, 2**32 - 1), st.sampled_from(["nan", "inf", "huge"]))
def test_decode_poisoned_equals_decode_sanitized(seed, mode):
    """make_decoder's in-graph hardening: decoding a poisoned stream ==
    decoding its sanitized version (and returns finite, 0/1 bits)."""
    rng = np.random.default_rng(seed)
    n = 6 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 3.0, rng)
    dec = make_decoder(DecoderConfig(spec=SPEC))
    bad = _poison(llr, rng, mode)
    clean, _ = sanitize_llr(bad)
    got = np.asarray(dec(bad, n))
    assert np.array_equal(got, np.asarray(dec(clean, n)))
    assert set(np.unique(got)) <= {0, 1}


@given(st.integers(0, 2**32 - 1), st.sampled_from(["nan", "inf", "huge"]))
def test_stream_push_poisoned_equals_sanitized_stream(seed, mode):
    """StreamDecoder.push sanitizes at the boundary: a poisoned chunk
    decodes exactly like the pre-sanitized stream, and the numeric
    counters record what was scrubbed."""
    rng = np.random.default_rng(seed)
    cfg = DecoderConfig(spec=SPEC)
    n = 12 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 3.0, rng)
    bad = llr.copy()
    bad[: 4 * SPEC.f] = _poison(llr[: 4 * SPEC.f], rng, mode)
    clean, n_bad = sanitize_llr(bad)
    assert n_bad > 0
    dec = make_stream_decoder(cfg, chunk_frames=4)
    out = [dec.push(bad[i: i + 4 * SPEC.f])
           for i in range(0, n, 4 * SPEC.f)]
    assert dec.numeric_stats()["sanitized_values"] == n_bad
    out.append(dec.flush())                 # (flush resets the counters)
    got = np.concatenate(out)[:n]
    assert np.array_equal(got, stream_decode(cfg, clean, n, chunk_frames=4))


def test_stream_push_rejects_malformed_shapes(rng):
    dec = make_stream_decoder(DecoderConfig(spec=SPEC), chunk_frames=4)
    assert dec.push(np.zeros((0, 2), np.float32)).size == 0   # empty: OK
    with pytest.raises(ValueError, match="flat or"):
        dec.push(np.zeros((2, 3, 2), np.float32))             # 3-D
    with pytest.raises(ValueError):
        dec.push(np.zeros((5, 3), np.float32))                # beta != 2
    # the decoder survives rejected pushes: a clean stream still decodes
    n = 6 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 4.0, rng)
    got = np.concatenate([dec.push(llr), dec.flush()])[:n]
    assert np.array_equal(got, stream_decode(DecoderConfig(spec=SPEC),
                                             llr, n, chunk_frames=4))


# ------------------------------------------------------- error hierarchy
def test_serve_error_hierarchy_and_retry_hint():
    for exc in (ServerFull, Backpressure, PoisonedInput,
                SessionQuarantined):
        assert issubclass(exc, ServeError)
    assert issubclass(ServeError, RuntimeError)     # old except-clauses
    srv = DecodeServer(slots=1, max_sessions=1, queue_depth=2)
    sid = srv.open_session(DecoderConfig(spec=SPEC), chunk_frames=2)
    with pytest.raises(ServerFull, match="max_sessions") as ei:
        srv.open_session(DecoderConfig(spec=SPEC))
    assert ei.value.retry_after_steps is None
    with pytest.raises(Backpressure, match="step") as ei:
        srv.push(sid, np.zeros((20 * SPEC.f, 2), np.float32))
    hint = ei.value.retry_after_steps
    assert isinstance(hint, int) and hint >= 1
    # the hint is honest: that many steps really do clear the condition
    srv.push(sid, np.zeros((4 * SPEC.f, 2), np.float32))
    with pytest.raises(Backpressure, match="split") as ei:
        srv.push(sid, np.zeros((4 * SPEC.f, 2), np.float32))
    for _ in range(ei.value.retry_after_steps):
        srv.step()
    srv.push(sid, np.zeros((4 * SPEC.f, 2), np.float32))


# ------------------------------------------------------- server hardening
def test_server_quarantines_poison_keeps_healthy_tenant_bit_exact(rng):
    cfg = DecoderConfig(spec=SPEC)
    n = 12 * SPEC.f
    healthy = noisy_llr(rng.integers(0, 2, n), STD_K7, 3.0, rng)
    srv = DecodeServer(slots=2, cache=PlanCache(), quarantine_after=2)
    bad_sid = srv.open_session(cfg, chunk_frames=4)
    ok_sid = srv.open_session(cfg, chunk_frames=4)
    per = 4 * SPEC.f
    raised = []
    for r in range(3):
        chunk = np.full((per, 2), np.nan, np.float32)
        try:
            srv.push(bad_sid, chunk)
        except SessionQuarantined as e:
            raised.append(e)
        srv.push(ok_sid, healthy[r * per:(r + 1) * per])
        while srv.step():
            pass
    assert len(raised) == 1 and raised[0].sid == bad_sid
    assert raised[0].strikes == 2 and raised[0].retry_after_steps is None
    with pytest.raises(SessionQuarantined):
        srv.poll(bad_sid)
    snap = srv.metrics_snapshot()
    assert snap["quarantined_sessions"] == 1
    assert snap["totals"]["quarantined"] == 1
    assert snap["totals"]["poisoned_pushes"] >= 2
    assert snap["totals"]["sanitized_values"] >= 2 * per * 2
    assert snap["totals"]["health"] == "impaired"
    st_bad = srv.session_state(bad_sid)
    assert st_bad["quarantined"] and st_bad["strikes"] == 2
    # the bucket-mate never noticed
    got = np.concatenate([srv.poll(ok_sid), srv.close_session(ok_sid)])[:n]
    assert np.array_equal(got, stream_decode(cfg, healthy, n,
                                             chunk_frames=4))
    bits = srv.close_session(bad_sid)           # teardown always works
    assert bits.dtype == np.int32 and srv.num_sessions == 0


def test_server_raise_policy_rejects_without_absorbing(rng):
    cfg = DecoderConfig(spec=SPEC)
    srv = DecodeServer(cache=PlanCache(), sanitize="raise")
    sid = srv.open_session(cfg, chunk_frames=4)
    n = 6 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 3.0, rng)
    bad = llr.copy()
    bad[0, 0] = np.inf
    with pytest.raises(PoisonedInput, match="non-finite"):
        srv.push(sid, bad)
    # nothing was absorbed: the clean retry decodes the whole stream
    srv.push(sid, llr)
    got = srv.close_session(sid)[:n]
    assert np.array_equal(got, stream_decode(cfg, llr, n, chunk_frames=4))


def test_server_counts_sanitize_scans(rng):
    """``sanitize_scans`` reads 0 on clean pushes and counts each push
    the sanitizer scrubbed (under 'zero') or refused (under 'raise');
    a push refused for its shape is no scan."""
    cfg = DecoderConfig(spec=SPEC)
    per = 4 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, per), STD_K7, 3.0, rng)
    trace = Tracer()
    srv = DecodeServer(cache=PlanCache(), quarantine_after=10, trace=trace)
    sid = srv.open_session(cfg, chunk_frames=4)

    def scans():
        return trace.counters().get("sanitize_scans", 0)

    for _ in range(3):
        srv.push(sid, llr)
        srv.step()
    assert scans() == 0
    for i, value in enumerate((np.nan, np.inf, -np.inf, 2 * LLR_CLIP)):
        bad = llr.copy()
        bad[5, 1] = value
        srv.push(sid, bad)
        srv.step()
        assert scans() == i + 1
    srv.close_session(sid)

    strict = DecodeServer(cache=PlanCache(), sanitize="raise",
                          quarantine_after=10, trace=Tracer())
    sid = strict.open_session(cfg, chunk_frames=4)
    strict.push(sid, llr)
    with pytest.raises(PoisonedInput):
        strict.push(sid, np.zeros((per, 3), np.float32))
    assert strict.trace.counters().get("sanitize_scans", 0) == 0
    bad = llr.copy()
    bad[0, 0] = np.nan
    with pytest.raises(PoisonedInput, match="non-finite"):
        strict.push(sid, bad)
    assert strict.trace.counters()["sanitize_scans"] == 1
    strict.close_session(sid)


def _run_faulted_server(rng, faults, n_chunks=3, **server_kw):
    """One session through a faulted server; returns (got, want, srv)."""
    cfg = DecoderConfig(spec=SPEC)
    n = n_chunks * 4 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 3.0, rng)
    srv = DecodeServer(slots=2, cache=PlanCache(), faults=faults,
                       backoff_s=0.0, **server_kw)
    sid = srv.open_session(cfg, chunk_frames=4)
    per = 4 * SPEC.f
    for r in range(n_chunks):
        srv.push(sid, llr[r * per:(r + 1) * per])
        while srv.step():
            pass
    got = np.concatenate([srv.poll(sid), srv.close_session(sid)])[:n]
    return got, stream_decode(cfg, llr, n, chunk_frames=4), srv


def test_server_retries_then_degrades_and_stays_correct(rng):
    """Every launch attempt fails -> retries exhaust -> the reference
    fallback serves the batch; the session's bits are still exactly the
    solo stream_decode result."""
    faults = FaultInjector(FaultSpec("launch_error", every=1), seed=0)
    got, want, srv = _run_faulted_server(rng, faults, max_retries=1)
    assert np.array_equal(got, want)
    tot = srv.metrics.totals()
    assert tot["degraded"] >= 1 and tot["health"] == "degraded"
    assert tot["launch_errors"] == 2 * tot["degraded"]   # 2 attempts each
    assert tot["retries"] == tot["degraded"]
    assert (srv.metrics_snapshot()["faults"]["injected"]["launch_error"]
            == tot["launch_errors"])


def test_server_deadline_timeout_degrades_and_stays_correct(rng):
    """A launch stuck past launch_timeout_s is treated as failed: with
    max_retries=0 it degrades immediately, bits stay exact."""
    faults = FaultInjector(
        FaultSpec("launch_slow", every=1, delay_s=0.05), seed=0)
    got, want, srv = _run_faulted_server(rng, faults, max_retries=0,
                                         launch_timeout_s=0.01)
    assert np.array_equal(got, want)
    tot = srv.metrics.totals()
    assert tot["timeouts"] >= 1 and tot["degraded"] >= 1
    assert tot["launch_errors"] == 0            # slow, not broken


def test_server_survives_forced_plan_cache_misses(rng):
    """Injected cache evictions force the cold path on a live server:
    rebuild + retrace, same bits."""
    faults = FaultInjector(FaultSpec("plan_cache_miss", every=2), seed=0)
    got, want, srv = _run_faulted_server(rng, faults)
    assert np.array_equal(got, want)
    tot = srv.metrics.totals()
    assert tot["cache_refreshes"] >= 1
    assert tot["degraded"] == 0 and tot["launch_errors"] == 0
    assert srv.cache.stats()["misses"] >= 1 + tot["cache_refreshes"]


def test_stream_decoder_fault_propagates_no_retry(rng):
    """The single-stream front-end has no retry layer: an injected
    launch fault reaches the caller (the server is the resilient tier)."""
    faults = FaultInjector(FaultSpec("launch_error", every=1), seed=0)
    dec = make_stream_decoder(DecoderConfig(spec=SPEC), chunk_frames=4,
                              faults=faults)
    llr = noisy_llr(rng.integers(0, 2, 8 * SPEC.f), STD_K7, 4.0, rng)
    with pytest.raises(InjectedKernelError):
        dec.push(llr)


# ------------------------------------------------------- renormalization
def test_renorm_every_bit_identical_on_clean_long_stream(rng):
    """Periodic (and disabled) path-metric renormalization is bit-
    identical to the per-stage default on a clean long stream — max-
    normalization only shifts all metrics by a constant."""
    cfg = DecoderConfig(spec=SPEC)                      # renorm_every=1
    n = 96 * SPEC.f
    llr = noisy_llr(rng.integers(0, 2, n), STD_K7, 2.0, rng)
    want = stream_decode(cfg, llr, n, chunk_frames=16)
    for every in (0, 7):
        got = stream_decode(dataclasses.replace(cfg, renorm_every=every),
                            llr, n, chunk_frames=16)
        assert np.array_equal(got, want), f"renorm_every={every}"


def test_renorm_every_validation():
    with pytest.raises(ValueError, match="renorm_every"):
        DecoderConfig(spec=SPEC, renorm_every=-1)
    with pytest.raises(ValueError, match="renormalize every stage"):
        DecoderConfig(spec=SPEC, backend="kernel", renorm_every=0)


# ------------------------------------------------------- kernel ops entry
def test_kernel_ops_entry_validation():
    from repro.kernels import ops
    frames = jnp.zeros((4, SPEC.frame_len, 2), jnp.float32)
    kw = dict(frames_per_tile=4, interpret=True)
    with pytest.raises(ValueError, match="2-D"):
        ops.viterbi_decode_frames(frames[0], STD_K7, SPEC, **kw)
    with pytest.raises(ValueError, match="frame_len"):
        ops.viterbi_decode_frames(frames[:, :-1], STD_K7, SPEC, **kw)
    with pytest.raises(ValueError, match="beta"):
        ops.viterbi_decode_frames(frames[..., :1], STD_K7, SPEC, **kw)
    with pytest.raises(ValueError, match="floating"):
        ops.viterbi_decode_frames(frames.astype(jnp.int32), STD_K7, SPEC,
                                  **kw)


# ------------------------------------------------------- bench gate CLI
def test_bench_gate_fails_fast_with_clear_error_on_corrupt_file(tmp_path):
    bad = tmp_path / "BENCH_corrupt.json"
    bad.write_text("{not json")
    env = dict(os.environ, BENCH_PATH=str(bad))
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "bench_gate.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=root)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "bench gate: ERROR" in proc.stdout
    assert "cannot be read" in proc.stdout
    assert "Traceback" not in proc.stdout + proc.stderr
    # and a structurally-valid file with an unexpected payload also gets
    # the clear message, not an IndexError downstream
    bad.write_text(json.dumps({"schema": "kernel_sweep/v2", "runs": 17}))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "bench_gate.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=root)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "bench gate: ERROR" in proc.stdout
