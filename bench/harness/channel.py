"""The traffic's data, made on the device from the seed in one jitted
call: random information bits, the configuration's encoder, puncturing,
BPSK and AWGN at the configuration's Eb/N0 (the verification chain of
``repro.channel.sim``, with the noise scaled for the code rate).

Each link gets a pool of pushes that it pushes over and over: a
continuous code is encoded tail-biting, so the repeated pool is a valid
code stream; a block code is encoded block by block from state 0 with
zero tail bits, so back-to-back blocks are too.

The configuration lists the channel's Eb/N0 levels (the operating point
and fades below it). Each link's pool pushes are dealt the levels
evenly, in an order drawn from the seed: every seed sends the same
channel mix."""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import codes


def prng_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (``jax.random.key``
    keeps only the low 32)."""
    s = int(seed) % (1 << 64)
    data = jnp.asarray([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def noise_sigma(cfg: dict, ebn0_db: float) -> float:
    """AWGN deviation for unit-energy BPSK at ``ebn0_db``: sigma^2 =
    1 / (2 R Eb/N0), R the code rate after puncturing."""
    ebn0 = 10.0 ** (float(ebn0_db) / 10.0)
    return float(np.sqrt(1.0 / (2.0 * codes.code_rate(cfg) * ebn0)))


def push_levels(seed: int, cfg: dict, links: int, pushes: int) -> np.ndarray:
    """(links, pushes) Eb/N0 in dB of each pool push: the configuration's
    levels dealt round, each link's pushes in an order from the seed."""
    levels = np.asarray(cfg["ebn0_db"], np.float64).reshape(-1)
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    dealt = levels[np.arange(pushes) % levels.size]
    return np.stack([dealt[rng.permutation(pushes)] for _ in range(links)])


@partial(jax.jit, static_argnames=("k", "polys", "links", "stages", "block",
                                   "tail"))
def _pool(key, keep, sigma, *, k, polys, links, stages, block, tail):
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (links, stages)).astype(jnp.int32)
    if block:
        # terminated blocks: the last `tail` bits of each block are 0 and
        # each block is encoded from state 0
        blocks = bits.reshape(links, stages // block, block)
        blocks = blocks.at[..., block - tail:].set(0)
        bits = blocks.reshape(links, stages)
        coded = codes.encode(blocks, k, list(polys), circular=False)
    else:
        coded = codes.encode(bits, k, list(polys), circular=True)
    sent = coded.reshape(links, -1)[:, keep]
    sigma = jnp.repeat(sigma, sent.shape[1] // sigma.shape[1], axis=1)
    rx = (1.0 - 2.0 * sent.astype(jnp.float32)
          + sigma * jax.random.normal(kn, sent.shape, jnp.float32))
    return bits.astype(jnp.int8), rx


def make_pool(seed: int, cfg: dict, links: int, pushes: int, stages: int,
              device=None):
    """(sent bits (links, pushes * stages) int8, received symbols
    (links, m) float32) as host arrays, for ``pushes`` pushes of
    ``stages`` trellis stages."""
    k, polys = codes.generators(cfg)
    sigma = np.vectorize(lambda e: noise_sigma(cfg, e))(
        push_levels(seed, cfg, links, pushes)).astype(np.float32)
    stages *= pushes
    blk = cfg.get("block")
    block, tail = (int(blk["bits"]), int(blk["tail"])) if blk else (0, 0)
    if block and stages % block:
        raise ValueError(f"pool of {stages} stages is not a whole number "
                         f"of {block}-bit blocks")
    keep = codes.keep_index(cfg, stages).astype(np.int32)
    with jax.default_device(device or jax.devices()[0]):
        bits, rx = _pool(prng_key(seed), keep, sigma, k=k,
                         polys=tuple(polys), links=links, stages=stages,
                         block=block, tail=tail)
        return np.asarray(bits), np.asarray(rx)
