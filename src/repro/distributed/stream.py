"""Sharded frame decode: the paper's tiling is also the distribution axis.

Frames are embarrassingly parallel (core/framed.py), so the multi-device
strategy is one line of placement: tile the frame axis of each chunk
across a 1-D 'frames' mesh with shard_map and run the per-device frame
decoder (reference or Pallas kernel backend) on each shard. Used by the
streaming front-end (core/stream.py, ``mesh=`` argument) so every pushed
chunk is decoded by all devices at once; the chunk size from
``kernels.autotune.plan_decode`` is a multiple of tiles x devices, so each
device receives whole kernel tiles.

The per-device VMEM budget of the tile plan is unchanged by sharding —
every device runs its own grid over its own frame shard — which is why
``plan_decode(num_devices=...)`` scales only the chunk geometry, not the
tile footprint.

The multi-tenant serve layer rides the same path: a ``DecodeServer``
built with ``mesh=...`` decodes each bucket's ``slots x chunk_frames``
batch through this sharded decoder (the batch IS the frame axis), and the
compiled-plan cache (serve/plan_cache.py) memoizes one sharded closure
per (cfg, mesh) so bucket churn re-uses the shard_map trace too.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core.pipeline import DecoderConfig, make_frame_decoder

__all__ = ["frame_mesh", "make_sharded_frame_decoder"]


def frame_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) local devices, axis 'frames'."""
    devs = np.array(jax.devices() if devices is None else devices)
    return Mesh(devs, ("frames",))


def make_sharded_frame_decoder(cfg: DecoderConfig, mesh: Mesh | None = None):
    """Returns decode_frames((F, L, beta)) -> (F, f) bits, frame-sharded.

    F is padded up to a multiple of the mesh size (padding frames decode
    garbage from zero LLRs and are dropped before returning). Each shard
    runs the ordinary per-device frame decoder (the cache-shared closure
    from make_frame_decoder), so every cfg backend — reference, unified
    kernel, split kernel — shards identically.
    """
    mesh = mesh if mesh is not None else frame_mesh()
    local = make_frame_decoder(cfg)
    ndev = int(mesh.devices.size)

    def decode_frames(frames: jax.Array) -> jax.Array:
        F = frames.shape[0]
        Fp = -(-F // ndev) * ndev
        if Fp != F:
            frames = jnp.pad(frames, ((0, Fp - F), (0, 0), (0, 0)))
        sharded = shard_map(local, mesh=mesh, in_specs=P("frames"),
                            out_specs=P("frames"), check_vma=False)
        return sharded(frames)[:F]

    return decode_frames
