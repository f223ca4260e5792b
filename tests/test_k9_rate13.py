"""The UMTS voice channel's code (3GPP TS 25.212 4.2.3.1: K=9, rate 1/3,
G0=557, G1=663, G2=711, 8 zero tail bits a block) through the serve path
on the CPU, with the unified Pallas kernel interpreted: 256 states and
three generators, named by the unpunctured rate ``1/3``.

The served bits are checked against two references: the program's jnp
oracle (``kernels.ref.unified_decode_frames_ref``) and the benchmark's
independent numpy decoder (``bench/references/framed_viterbi.py``). One
frame geometry is used throughout (the benchmark cell's), so the kernel
compiles once for the whole file."""
import importlib.util
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DecoderConfig, FrameSpec, STD_K7, make_trellis
from repro.core.framed import frame_llr
from repro.core.puncture import puncture
from repro.core.stream import StreamContext, stream_decode
from repro.kernels.ref import unified_decode_frames_ref
from repro.serve import DecodeServer

from conftest import noisy_llr

POLYS = (0o557, 0o663, 0o711)
K9 = make_trellis(9, POLYS)
K5 = make_trellis(5, (0o23, 0o35))
#: one AMR 12.2k transport block (244 + 16 CRC bits) and its 8 tail bits
BLOCK, TAIL = 268, 8
SPEC = FrameSpec(f=268, v1=36, v2=54, f0=67, v2s=54)
SPEC12 = FrameSpec(f=64, v1=16, v2=20)
SPEC34 = FrameSpec(f=63, v1=21, v2=21)
KERNEL = dict(backend="kernel", interpret=True)
CFG = DecoderConfig(trellis=K9, spec=SPEC, rate="1/3", **KERNEL)
DATA = os.path.join(os.path.dirname(__file__), "data")


def _framed_viterbi():
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "references", "framed_viterbi.py")
    spec = importlib.util.spec_from_file_location("framed_viterbi", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _blocks(nblocks, seed):
    """Sent bits of ``nblocks`` terminated blocks, back to back: each
    ends in TAIL zeros, so the stream encodes from state 0 throughout."""
    bits = np.random.default_rng(seed).integers(0, 2, (nblocks, BLOCK))
    bits[:, BLOCK - TAIL:] = 0
    return bits.reshape(-1)


def _references(llr, n):
    """The framed decode of the whole stream by both references."""
    frames = frame_llr(jnp.asarray(llr), SPEC)
    ref = np.asarray(unified_decode_frames_ref(frames, K9, SPEC))
    plain = _framed_viterbi().decode_frames(
        np.asarray(frames), 9, list(POLYS),
        {"f": SPEC.f, "v1": SPEC.v1, "f0": SPEC.f0, "v2s": SPEC.v2s})
    return ref.reshape(-1)[:n], plain.reshape(-1)[:n]


def _serve(srv, sid, pieces, n):
    """Push ``pieces`` one by one, stepping between pushes, then close
    the session (which flushes the last partial chunk)."""
    out = []
    for p in pieces:
        srv.push(sid, p)
        srv.step()
        out.append(srv.poll(sid))
    out.append(srv.close_session(sid))
    return np.concatenate(out)[:n]


def _uneven(llr):
    """Stage slices of uneven sizes, one of them flat."""
    cuts = [0, 250, 251, 900, llr.shape[0]]
    pieces = [llr[a:b] for a, b in zip(cuts, cuts[1:])]
    pieces[2] = pieces[2].reshape(-1)              # (m * beta,) flat push
    return pieces


def test_served_stream_matches_both_references():
    """(a) Terminated 268-stage blocks pushed in uneven slices decode
    bit-identically to the framed decode of the whole stream, by the jnp
    oracle and by the plain numpy reference; ``close_session`` flushes
    the last partial chunk."""
    bits = _blocks(6, seed=1)
    llr = noisy_llr(bits, K9, -2.0, np.random.default_rng(11))
    srv = DecodeServer(slots=1)
    sid = srv.open_session(CFG, chunk_frames=2)
    got = _serve(srv, sid, _uneven(llr), bits.size)
    ref, plain = _references(llr, bits.size)
    assert got.shape == (bits.size,)
    assert np.array_equal(got, ref)
    assert np.array_equal(got, plain)
    assert np.count_nonzero(got != bits) > 0        # the noise is real


def test_noise_free_stream_decodes_the_sent_bits():
    """(b) Clean BPSK symbols decode to exactly the sent bits."""
    bits = _blocks(4, seed=2)
    coded = np.asarray(noisy_llr(bits, K9, 300.0, np.random.default_rng(0)))
    llr = np.sign(coded).astype(np.float32)
    srv = DecodeServer(slots=1)
    sid = srv.open_session(CFG, chunk_frames=2)
    assert np.array_equal(_serve(srv, sid, _uneven(llr), bits.size), bits)


@pytest.mark.parametrize("trellis,rate", [(K9, "3/4"), (K9, "1/2"),
                                          (STD_K7, "1/3")],
                         ids=["r34-on-beta3", "r12-on-beta3",
                              "r13-on-beta2"])
def test_rate_must_match_the_trellis(trellis, rate):
    """(c) A pattern whose row count is not the trellis's beta is
    refused, naming both, by the config and by the stream context."""
    with pytest.raises(ValueError, match=f"beta={trellis.beta}"):
        DecoderConfig(trellis=trellis, rate=rate)
    with pytest.raises(ValueError, match=rate):
        StreamContext(SPEC, trellis.beta, 1, rate)


@pytest.mark.parametrize("trellis,rate,want", [
    (STD_K7, "1/2", "1/2"), (STD_K7, None, "1/2"), (K9, None, "1/3"),
    (K9, "1/3", "1/3"), (STD_K7, "3/4", "3/4")])
def test_default_rate_is_the_trellis_own(trellis, rate, want):
    """(c) The default rate is 1/beta: "1/2" on a rate-1/2 trellis as
    before, "1/3" on a rate-1/3 one; configs that differ only in how the
    unpunctured rate was named are one config (one bucket, one plan)."""
    spec = SPEC34 if want == "3/4" else SPEC12
    kw = {} if rate is None else {"rate": rate}
    cfg = DecoderConfig(trellis=trellis, spec=spec, **kw)
    assert cfg.rate == want
    assert cfg.punctured == (want == "3/4")
    assert cfg == DecoderConfig(trellis=trellis, spec=spec, rate=want)
    ctx = StreamContext(spec, trellis.beta, 1, rate)
    assert ctx.rate == want and ctx.punctured == cfg.punctured


@pytest.mark.parametrize("version", [1, 2])
def test_rate13_context_state_round_trip(version):
    """(d) A rate-1/3 context snapshotted mid-stage and restored into a
    fresh one emits the same windows as the uninterrupted context."""
    llr = np.random.default_rng(5).standard_normal((3 * BLOCK, 3)) \
        .astype(np.float32)
    flat = llr.reshape(-1)
    a = StreamContext(SPEC, 3, 1)
    a.append(flat[:600])                           # 200 of 804 stages
    state = a.state_dict(version=version)
    assert state["geometry"]["rate"] == "1/3"
    b = StreamContext(SPEC, 3, 1)
    b.load_state(json.loads(json.dumps(state)))
    for ctx in (a, b):
        ctx.append(flat[600:])
    got = [[(w.window.tobytes(), w.n_bits)
            for w in ctx.take_windows() + ctx.flush_chunks()]
           for ctx in (a, b)]
    assert got[0] == got[1] and len(got[0]) == 3


def test_rate13_server_checkpoint_round_trip(tmp_path):
    """(d) A K=9 rate-1/3 server checkpointed mid-stream and restored
    decodes the rest to the same bits as the uninterrupted stream."""
    bits = _blocks(5, seed=3)
    llr = noisy_llr(bits, K9, 2.0, np.random.default_rng(13))
    srv = DecodeServer(slots=1)
    sid = srv.open_session(CFG, chunk_frames=2)
    srv.push(sid, llr[:700])
    srv.step()
    path = srv.checkpoint(str(tmp_path / "k9.json"))
    srv2 = DecodeServer.restore(path)
    saved = json.loads(open(path).read())["payload"]["sessions"][0]
    assert saved["cfg"]["rate"] == "1/3"
    assert saved["ctx"]["geometry"]["rate"] == "1/3"
    ref, _ = _references(llr, bits.size)
    for s in (srv, srv2):                   # the saved bits ride along
        assert np.array_equal(_serve(s, sid, [llr[700:]], bits.size), ref)


def test_rate12_checkpoint_of_the_older_format_restores(tmp_path):
    """(d) ``data/checkpoint_rate12_k7.json`` was written before rates
    were resolved from the trellis: a K=7 rate-1/2 and a K=7 rate-3/4
    session, cut mid-stream. It restores unchanged and decodes the rest
    of each stream to the uninterrupted decode's bits. Its input is
    re-made here from the seed it was written with."""
    rng = np.random.default_rng(2026)
    rx12 = rng.normal(1.0, 1.0, (5 * 64, 2)).astype(np.float32)
    rx34 = rng.normal(1.0, 1.0, 420).astype(np.float32)
    srv = DecodeServer.restore(os.path.join(DATA, "checkpoint_rate12_k7.json"))
    cfgs = {sid: srv._session(sid).cfg for sid in (0, 1)}
    assert [cfgs[s].rate for s in (0, 1)] == ["1/2", "3/4"]
    srv.push(0, rx12[3 * 64 + 5:])
    srv.push(1, rx34[301:])
    srv.drain()
    got = {sid: np.concatenate([srv.poll(sid), srv.close_session(sid)])
           for sid in (0, 1)}
    assert np.array_equal(got[0][:320], stream_decode(
        cfgs[0], rx12, 320, chunk_frames=2))
    assert np.array_equal(got[1][:315], stream_decode(
        cfgs[1], rx34, 315, chunk_frames=2))


def test_three_trellises_share_one_server():
    """(e) K=5 rate 1/2, K=7 rate 3/4 and K=9 rate 1/3 on one server:
    three buckets, one per trellis, each stripped to its own 1/beta, and
    each session decodes exactly."""
    rng = np.random.default_rng(7)
    n9 = 4 * BLOCK
    llr9 = noisy_llr(_blocks(4, seed=4), K9, 2.0, rng)
    n5 = 6 * SPEC12.f
    llr5 = noisy_llr(rng.integers(0, 2, n5), K5, 3.0, rng)
    n7 = 6 * SPEC34.f
    coded7 = noisy_llr(rng.integers(0, 2, n7), STD_K7, 4.0, rng)
    raw7 = np.asarray(puncture(jnp.asarray(coded7), "3/4"))
    cfg5 = DecoderConfig(trellis=K5, spec=SPEC12, **KERNEL)
    cfg7 = DecoderConfig(spec=SPEC34, rate="3/4", **KERNEL)
    srv = DecodeServer(slots=1)
    sids = [srv.open_session(CFG, chunk_frames=2),
            srv.open_session(cfg5, chunk_frames=2),
            srv.open_session(cfg7, chunk_frames=2)]
    streams = [llr9, llr5, raw7]
    out = {sid: [] for sid in sids}
    for lo, hi in ((0, 0.4), (0.4, 1.0)):
        for sid, s in zip(sids, streams):
            m = len(s)                             # stages, or raw symbols
            srv.push(sid, s[int(lo * m):int(hi * m)])
        srv.step()
        for sid in sids:
            out[sid].append(srv.poll(sid))
    got = {sid: np.concatenate(out[sid] + [srv.close_session(sid)])
           for sid in sids}
    assert sorted(b.decode_cfg.rate for b in srv.buckets()) == \
        ["1/2", "1/2", "1/3"]
    assert len({b.decode_cfg.trellis for b in srv.buckets()}) == 3
    assert np.array_equal(got[sids[0]][:n9], _references(llr9, n9)[0])
    for sid, cfg, s, n in ((sids[1], cfg5, llr5, n5),
                           (sids[2], cfg7, raw7, n7)):
        want = stream_decode(
            DecoderConfig(trellis=cfg.trellis, spec=cfg.spec, rate=cfg.rate,
                          backend="reference"), s, n, chunk_frames=2)
        assert np.array_equal(got[sid][:n], want)
