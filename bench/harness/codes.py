"""The configurations' codes, from their generator polynomials and
puncturing masks as the standards state them: tap lists, the on-device
encoder and the puncturing of the coded stream into the symbol stream a
receiver pushes. Independent of the system under test."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def generators(cfg: dict) -> tuple[int, list[int]]:
    """(K, generator polynomials) in the configuration's symbol order."""
    code = cfg["code"]
    return int(code["k"]), [int(p, 8) for p in code["polys_octal"]]


def taps(k: int, poly: int) -> list[int]:
    """The delays j (0 = the current input bit) that ``poly`` adds. The
    polynomial's most significant of its k bits is the current input."""
    return [j for j in range(k) if (poly >> (k - 1 - j)) & 1]


def puncture_mask(cfg: dict) -> np.ndarray:
    """(beta, period) 0/1 mask: symbol b of stage t is sent when
    mask[b, t % period] is 1. Unpunctured codes send every symbol."""
    k, polys = generators(cfg)
    mask = cfg["code"].get("puncture")
    if mask is None:
        return np.ones((len(polys), 1), np.int64)
    mask = np.asarray(mask, np.int64)
    if mask.shape[0] != len(polys):
        raise ValueError(f"puncture mask has {mask.shape[0]} rows for "
                         f"{len(polys)} generators")
    return mask


def code_rate(cfg: dict) -> float:
    """Information bits per sent symbol."""
    mask = puncture_mask(cfg)
    return mask.shape[1] / mask.sum()


def symbols_per_stages(cfg: dict, stages: int) -> int:
    """Sent symbols of ``stages`` trellis stages (a whole number of
    puncturing periods)."""
    mask = puncture_mask(cfg)
    period = mask.shape[1]
    if stages % period:
        raise ValueError(f"{stages} stages are not a whole number of "
                         f"puncturing periods ({period})")
    return stages // period * int(mask.sum())


def keep_index(cfg: dict, stages: int) -> np.ndarray:
    """Indices of the sent symbols in the row-major (stages, beta) coded
    array: within a stage, generator order."""
    mask = puncture_mask(cfg)
    reps = -(-stages // mask.shape[1])
    full = np.tile(mask, (1, reps))[:, :stages].T         # (stages, beta)
    return np.flatnonzero(full.reshape(-1))


def encode(bits, k: int, polys: list[int], circular: bool):
    """(..., n) {0,1} bits -> (..., n, beta) coded bits, in JAX.

    ``circular`` encodes tail-biting: the encoder starts in the state
    its last k-1 inputs leave, so a repeated block is a valid code
    stream. Otherwise it starts in state 0."""
    bits = bits.astype(jnp.int32)
    n = bits.shape[-1]

    def delayed(j):
        if j == 0:
            return bits
        if circular:
            return jnp.roll(bits, j, axis=-1)
        pad = jnp.zeros(bits.shape[:-1] + (j,), jnp.int32)
        return jnp.concatenate([pad, bits[..., :n - j]], axis=-1)

    outs = []
    for g in polys:
        acc = jnp.zeros_like(bits)
        for j in taps(k, g):
            acc = acc ^ delayed(j)
        outs.append(acc)
    return jnp.stack(outs, axis=-1)
