"""Plain reference: framed Viterbi decoding with parallel traceback, in
numpy on the host, written from the algorithm and independent of the
system under test.

The stream of n trellis stages is cut into frames of f kept stages.
Frame m runs the add-compare-select recursion over stages
[m f - v1, (m+1) f + v2) (zero LLRs outside the stream) from all-zero
path metrics, normalizing by the stage maximum after every stage. Its
kept region is split into f/f0 subframes; subframe q is traced back from
stage v1 + (q+1) f0 - 1 + v2s, starting at that stage's best state, and
keeps its last f0 decoded bits.

Conventions of the algorithm (they decide ties, so they are part of the
result): a state is the last k-1 input bits, the newest in the most
significant bit; state j is entered from 2(j mod S/2) and 2(j mod S/2)+1
with input bit j >> (k-2); a tie between the two candidates goes to the
odd predecessor; the best state is the first of equal maxima. A branch
metric is sum_b (1 - 2 c_b) llr_b over the edge's coded bits c_b, added
in generator order in float32; path metrics are float32."""
from __future__ import annotations

import numpy as np

#: frames decoded together: bounds the survivor memory
BLOCK_FRAMES = 2048


def _edges(k: int, polys: list[int]):
    """(pred (S, 2), input bit (S,), coded word (S, 2)): the two edges
    into each state; the word's bit beta-1-b is generator b's output."""
    S = 1 << (k - 1)
    j = np.arange(S)
    pred = np.stack([2 * (j % (S // 2)), 2 * (j % (S // 2)) + 1], axis=1)
    bit = j >> (k - 2)
    beta = len(polys)
    word = np.zeros((S, 2), np.int64)
    for p in (0, 1):
        reg = (bit << (k - 1)) | pred[:, p]        # k bits, newest input first
        for b, g in enumerate(polys):
            par = np.array([bin(int(x)).count("1") & 1 for x in reg & g])
            word[:, p] |= par << (beta - 1 - b)
    return pred, bit, word


def _branch_table(llr: np.ndarray, dtype) -> np.ndarray:
    """(..., beta) llr -> (..., 2^beta) metric of each coded word, summed
    over the generators in order in ``dtype``."""
    llr = llr.astype(dtype)
    beta = llr.shape[-1]
    out = []
    for o in range(1 << beta):
        acc = None
        for b in range(beta):
            term = -llr[..., b] if (o >> (beta - 1 - b)) & 1 else llr[..., b]
            acc = term if acc is None else (acc + term).astype(dtype)
        out.append(acc)
    return np.stack(out, axis=-1).astype(dtype)


def decode_frames(frames: np.ndarray, k: int, polys: list[int],
                  spec: dict, dtype=np.float32) -> np.ndarray:
    """(F, L, beta) LLR frames -> (F, f) decoded bits (int8). Metrics are
    computed in ``dtype``: float32 as the configurations state, or a
    lower precision for the control of the correctness check."""
    f, v1, f0, v2s = spec["f"], spec["v1"], spec["f0"], spec["v2s"]
    out = [_decode_block(frames[i:i + BLOCK_FRAMES], k, polys, f, v1, f0,
                         v2s, dtype)
           for i in range(0, frames.shape[0], BLOCK_FRAMES)]
    return np.concatenate(out) if out else np.zeros((0, f), np.int8)


def _decode_block(frames, k, polys, f, v1, f0, v2s, dtype):
    F, L, _ = frames.shape
    S = 1 << (k - 1)
    pred, bit, word = _edges(k, polys)
    bm = _branch_table(np.asarray(frames, np.float32), dtype)
    sigma = np.zeros((F, S), dtype)
    sel = np.empty((L, F, S), np.bool_)
    best = np.empty((L, F), np.int64)
    for t in range(L):
        c0 = sigma[:, pred[:, 0]] + bm[:, t, word[:, 0]]
        c1 = sigma[:, pred[:, 1]] + bm[:, t, word[:, 1]]
        s = c1 >= c0
        new = np.where(s, c1, c0)
        new = new - new.max(axis=1, keepdims=True)
        sel[t] = s
        best[t] = new.argmax(axis=1)
        sigma = new
    nsub = f // f0
    ends = v1 + (np.arange(nsub) + 1) * f0 - 1 + v2s      # (nsub,)
    state = best[ends][:, :].T.copy()                     # (F, nsub)
    rows = np.arange(F)[:, None]
    bits = np.empty((f0 + v2s, F, nsub), np.int8)
    for r in range(f0 + v2s):
        bits[r] = state >> (k - 2)
        p = sel[ends[None, :] - r, rows, state]
        state = pred[state, p.astype(np.int64)]
    kept = bits[v2s:][::-1]                               # (f0, F, nsub)
    return kept.transpose(1, 2, 0).reshape(F, f)


def depuncture(rx: np.ndarray, mask: np.ndarray, stages: int) -> np.ndarray:
    """(links, m) sent-symbol stream -> (links, stages, beta) LLRs with
    zeros where the mask drops a symbol."""
    beta, period = mask.shape
    full = np.tile(mask, (1, -(-stages // period)))[:, :stages].T
    keep = np.flatnonzero(full.reshape(-1))
    out = np.zeros((rx.shape[0], stages * beta), np.float32)
    out[:, keep] = rx
    return out.reshape(rx.shape[0], stages, beta)


def expected(rx: np.ndarray, k: int, polys: list[int], mask: np.ndarray,
             spec: dict, stages: int,
             dtype=np.float32) -> tuple[np.ndarray, np.ndarray]:
    """The decode of each link's endless stream of its pool repeated.

    Returns (first (links, f), steady (links, stages)): the stream's bit
    p is first[p] for p < f (frame 0 sees zeros to its left), else
    steady[p % stages] (every other frame sees the pool's own
    neighbours, cyclically)."""
    f, v1 = spec["f"], spec["v1"]
    L = v1 + f + spec["v2"]
    if stages % f:
        raise ValueError(f"pool of {stages} stages is not whole frames")
    llr = depuncture(rx, mask, stages)                    # (links, n, beta)
    links, nf = llr.shape[0], stages // f
    idx = (np.arange(nf)[:, None] * f - v1 + np.arange(L)[None, :]) % stages
    frames = llr[:, idx]                                  # (links, nf, L, b)
    first = frames[:, 0].copy()
    first[:, :v1] = 0.0
    allf = np.concatenate([frames, first[:, None]], axis=1)
    bits = decode_frames(allf.reshape(links * (nf + 1), L, -1), k, polys,
                         spec, dtype).reshape(links, nf + 1, f)
    return bits[:, nf], bits[:, :nf].reshape(links, stages)
