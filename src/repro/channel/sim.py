"""Verification system of paper Fig. 8 (§V-B).

bits -> convolutional encoder -> (puncture) -> BPSK -> AWGN(Eb/N0)
     -> (depuncture) -> decoder -> BER vs. the original bits.

Also provides the theoretical union-bound BER curve the paper compares
against (their MATLAB ``bertool`` reference) and the paper's "distance in
Eb/N0" metric used by Tables II/III.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import numpy as np
import scipy.special as sps
import jax
import jax.numpy as jnp

from ..core.encoder import encode
from ..core.puncture import check_rate, depuncture, puncture, punctured_rate
from ..core.trellis import Trellis, STD_K7

__all__ = ["bpsk", "awgn", "ber", "simulate", "theoretical_ber",
           "ebn0_distance_metric"]


def bpsk(bits: jax.Array) -> jax.Array:
    """bit 0 -> +1.0, bit 1 -> -1.0 (matches the LLR sign convention)."""
    return 1.0 - 2.0 * bits.astype(jnp.float32)


def awgn(key: jax.Array, x: jax.Array, ebn0_db: float) -> jax.Array:
    """AWGN with sigma = 10^(-EbN0dB/20), the paper's simulation recipe."""
    sigma = 10.0 ** (-ebn0_db / 20.0)
    return x + sigma * jax.random.normal(key, x.shape, jnp.float32)


def ber(decoded: jax.Array, truth: jax.Array) -> jax.Array:
    return jnp.mean((decoded != truth).astype(jnp.float32))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _channel(key, n: int, ebn0_db: float, rate: str, trellis: Trellis):
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (n,)).astype(jnp.int32)
    coded = encode(bits, trellis)                     # (n, beta)
    tx = bpsk(puncture(coded, rate))                  # punctured stream
    rx = awgn(kn, tx, ebn0_db)                        # soft symbols ~ LLRs
    llr = depuncture(rx, rate, n)                     # (n, beta), 0 = erased
    return bits, llr


def simulate(key: jax.Array, n: int, ebn0_db: float,
             decoder: Callable[[jax.Array], jax.Array],
             rate: str | None = None, trellis: Trellis = STD_K7,
             hard: bool = False):
    """Run Fig. 8 once; returns (ber, bits, decoded).

    ``decoder`` maps (n, beta) llr -> (n,) bits — any of: full reference,
    framed (serial/parallel traceback), or the Pallas unified kernel.
    ``hard=True`` slices the soft symbols to ±1 (hard-decision mode,
    paper §II-C — costs ~2.3 dB of BER).
    BER is trustworthy only when it exceeds 100/n (paper's rule of thumb).
    ``rate`` None is the trellis's unpunctured 1/beta.
    """
    bits, llr = _channel(key, n, ebn0_db, check_rate(rate, trellis.beta),
                         trellis)
    if hard:
        llr = jnp.sign(llr)
    decoded = decoder(llr)
    return float(ber(decoded, bits)), bits, decoded


# ---------------------------------------------------------------------------
# Theory: union bound for the standard K=7 (171,133) code. Distance spectrum
# coefficients c_d (information-bit weights) from the literature.
_SPECTRUM_K7 = {10: 36, 12: 211, 14: 1404, 16: 11633, 18: 77433, 20: 502690}


def _q(x):
    return 0.5 * sps.erfc(np.asarray(x) / np.sqrt(2.0))


def theoretical_ber(ebn0_db: np.ndarray, rate: float = 0.5,
                    spectrum: dict = _SPECTRUM_K7) -> np.ndarray:
    """Union-bound BER for soft-decision ML decoding (tight above ~4 dB)."""
    ebn0 = 10.0 ** (np.asarray(ebn0_db, dtype=np.float64) / 10.0)
    out = np.zeros_like(ebn0)
    for d, c in spectrum.items():
        out = out + c * _q(np.sqrt(2.0 * d * rate * ebn0))
    return out


def ebn0_distance_metric(ebn0_db: np.ndarray, ber_meas: np.ndarray,
                         rate: float = 0.5) -> float:
    """Paper Tables II/III metric: horizontal (Eb/N0) distance between the
    measured BER curve and the theoretical one, averaged over the overlap.

    For each measured (ebn0, ber) point, find the Eb/N0 at which theory
    reaches the same BER and average the dB gaps.
    """
    grid = np.linspace(0.0, 12.0, 1201)
    th = theoretical_ber(grid, rate)
    gaps = []
    for e, b in zip(np.asarray(ebn0_db), np.asarray(ber_meas)):
        if b <= 0 or b >= 0.4:
            continue
        # theory BER is monotonically decreasing in Eb/N0
        idx = np.searchsorted(-np.log10(th), -np.log10(b))
        idx = min(max(idx, 0), len(grid) - 1)
        gaps.append(e - grid[idx])
    return float(np.mean(gaps)) if gaps else float("nan")
