"""Puncturing / de-puncturing (paper §IV-E).

Standard DVB/GSM-style puncturing patterns over the rate-1/2 mother code.
A pattern is a (beta, period) 0/1 mask; 0-marked symbols are dropped by the
transmitter and re-inserted as neutral zero-LLRs by the receiver
("depuncturing" — zeros contribute nothing to eq. 2's branch metrics).
``1/n`` names the unpunctured code of n generators (every symbol kept), so
a trellis of beta generators runs ``1/beta`` when it is not punctured; a
rate is valid on a trellis only where its pattern has beta rows
(``check_rate``).

Frames must start at a pattern boundary (paper: f, v1, v2 multiples of the
mask period) — enforced by ``check_alignment``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["PATTERNS", "pattern", "unpunctured", "check_rate", "puncture",
           "depuncture", "check_alignment", "punctured_rate"]

# pattern[b, t]: keep output bit b at phase t (mother code beta=2)
PATTERNS: dict[str, np.ndarray] = {
    "1/2": np.array([[1], [1]], dtype=np.int32),
    "2/3": np.array([[1, 1], [1, 0]], dtype=np.int32),
    "3/4": np.array([[1, 1, 0], [1, 0, 1]], dtype=np.int32),
}


def unpunctured(beta: int) -> str:
    """The name of the unpunctured code of ``beta`` generators."""
    return f"1/{beta}"


def pattern(name: str) -> np.ndarray:
    """The (beta, period) keep mask ``name`` stands for: a table pattern,
    or for ``1/n`` all n symbols of every stage."""
    if name in PATTERNS:
        return PATTERNS[name]
    num, _, den = str(name).partition("/")
    if num == "1" and den.isdigit() and int(den) >= 2:
        return np.ones((int(den), 1), np.int32)
    raise ValueError(f"unknown rate {name!r}: neither 1/n nor one of "
                     f"{sorted(PATTERNS)}")


def check_rate(rate: str | None, beta: int) -> str:
    """``rate`` as a trellis of ``beta`` generators runs it: None is the
    unpunctured ``1/beta``; a pattern with another row count than
    ``beta`` raises ValueError."""
    if rate is None:
        return unpunctured(beta)
    rows = pattern(rate).shape[0]
    if rows != beta:
        raise ValueError(f"rate {rate!r} is a pattern over {rows} "
                         f"generators, but the trellis has beta={beta}")
    return rate


def punctured_rate(name: str) -> float:
    p = pattern(name)
    return p.shape[1] / p.sum()


def _mask_for(n: int, pattern: np.ndarray) -> np.ndarray:
    beta, period = pattern.shape
    reps = -(-n // period)
    return np.tile(pattern, (1, reps)).T[:n]          # (n, beta)


def puncture(coded: jax.Array, name: str) -> jax.Array:
    """(n, beta) symbols -> (m,) punctured flat stream (static shapes)."""
    n = coded.shape[0]
    mask = _mask_for(n, pattern(name)).reshape(-1).astype(bool)   # (n*beta,)
    flat = coded.reshape(-1)
    # static-shape compaction: the kept positions are known at trace time
    keep_idx = np.nonzero(mask)[0]
    return flat[jnp.asarray(keep_idx)]


def depuncture(stream: jax.Array, name: str, n: int) -> jax.Array:
    """(m,) received symbols -> (n, beta) llr grid with neutral zeros.

    Parallel: a single static scatter (every thread/lane handles its own
    symbols independently, as in the paper's GPU version).
    """
    pat = pattern(name)
    mask = _mask_for(n, pat).reshape(-1).astype(bool)
    keep_idx = np.nonzero(mask)[0]
    assert stream.shape[0] == keep_idx.shape[0], (
        f"stream length {stream.shape[0]} != expected {keep_idx.shape[0]}")
    flat = jnp.zeros((n * pat.shape[0],), stream.dtype)
    flat = flat.at[jnp.asarray(keep_idx)].set(stream)
    return flat.reshape(n, pat.shape[0])


def check_alignment(f: int, v1: int, v2: int, name: str) -> None:
    """Paper §IV-E: f, v1, v2 must be multiples of the pattern period so all
    frames start at a mask boundary (avoids block divergence)."""
    period = pattern(name).shape[1]
    for nm, v in (("f", f), ("v1", v1), ("v2", v2)):
        if v % period:
            raise ValueError(f"{nm}={v} not a multiple of pattern period "
                             f"{period} for rate {name}")
