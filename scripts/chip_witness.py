#!/usr/bin/env python3
"""Witnesses of the TPU compiler behaviours the decode path works around.

    python scripts/chip_witness.py [--seed N] [--frames 4096,8192,...]

Runs on one TPU and prints one line per finding, then a JSON summary as
its last line. Without a TPU it prints no result and exits 1.

  argmax   Mosaic's ``argmax`` over sublanes on a (64, 128) input with
           tied maxima, against the first-index argmax the reference
           takes; and the unified kernel's replacement (the first state
           whose normalized metric is exactly 0).
  matmul   a float32 (n, 2) LLR x (2, 2) ±1 matmul at default precision
           against the exact signed sums: the TPU rounds matmul inputs to
           bfloat16. ``core.metrics`` computes the signed sums instead.
  flatten  the reference decode (``decode_frame`` vmapped over frames) of
           F frames of the K=7 code at the chip smoke's phase-A geometry,
           for each F of the sweep, in several forms:
             frames    returns (F, f), flattened on the host;
             in_jit    flattens to (F*f,) inside the same program;
             second    flattens by a second program on the device;
             barrier   flattens after ``lax.optimization_barrier``;
             decoder   ``make_decoder(backend='reference')``;
             window    the stream path's window program
                       (``PLAN_CACHE.window_decoder``).
           Each form is compared bit for bit with ``frames``, and
           ``frames`` with the same decode on the host CPU at F = 8192
           (the largest F when the sweep lacks it).

Exit code 0 when the forms the library uses are exact: the kernel's
argmax replacement, the signed sums, and the ``decoder`` and ``window``
forms at every F. The compiler behaviours themselves are reported, not
asserted: a later compiler may fix them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np                                       # noqa: E402
import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
from jax.experimental import pallas as pl                # noqa: E402

from repro.channel.sim import _channel                   # noqa: E402
from repro.compile_cache import use_compile_cache       # noqa: E402
from repro.core import (DecoderConfig, FrameSpec, STD_K7,  # noqa: E402
                        make_decoder)
from repro.core.framed import decode_frame, frame_llr    # noqa: E402
from repro.core.metrics import _signed_sums              # noqa: E402
from repro.serve.plan_cache import PLAN_CACHE            # noqa: E402

SPEC_A = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
EBN0_DB = 3.0
SWEEP = (4096, 6144, 8192, 12288, 16384)


def log(*parts) -> None:
    print(*parts, flush=True)


# -- argmax ------------------------------------------------------------------
def _argmax_kernel(x_ref, mosaic_ref, first_ref):
    x = x_ref[...]                                   # (S, 128)
    mosaic_ref[...] = jnp.argmax(x, axis=0, keepdims=True).astype(jnp.int32)
    ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    norm = x - jnp.max(x, axis=0, keepdims=True)
    first_ref[...] = jnp.min(jnp.where(norm == 0.0, ids, x.shape[0]),
                             axis=0, keepdims=True)


def argmax_witness(key) -> dict:
    S, lanes = 64, 128
    # three levels: most columns hold several tied maxima
    x = jax.random.randint(key, (S, lanes), 0, 3).astype(jnp.float32)
    out = jax.ShapeDtypeStruct((1, lanes), jnp.int32)
    mosaic, first = pl.pallas_call(_argmax_kernel,
                                   out_shape=(out, out))(x)
    want = np.argmax(np.asarray(x), axis=0)
    xs = np.asarray(x)
    r = {"columns": lanes,
         "ties": int(((xs == xs.max(0)).sum(0) > 1).sum()),
         "mosaic_argmax_differs": int((np.asarray(mosaic)[0] != want).sum()),
         "first_zero_differs": int((np.asarray(first)[0] != want).sum())}
    log(f"argmax: {r['ties']} of {lanes} columns tied; Mosaic argmax "
        f"differs from the first index in {r['mosaic_argmax_differs']}, "
        f"the kernel's first-zero form in {r['first_zero_differs']}")
    return r


# -- matmul ------------------------------------------------------------------
def matmul_witness(key, n: int = 1 << 16) -> dict:
    llr = jax.random.normal(key, (n, 2), jnp.float32) * 2.0
    signs = jnp.asarray(STD_K7.out_signs[:2], jnp.float32)   # (2, beta)
    mm = jax.jit(lambda a: a @ signs.T)(llr)
    ss = jax.jit(lambda a: _signed_sums(a, signs))(llr)
    a = np.asarray(llr)
    exact = np.stack([a[:, 0] * s[0] + a[:, 1] * s[1]
                      for s in np.asarray(signs)], axis=1).astype(np.float32)
    r = {"values": int(exact.size),
         "matmul_differs": int((np.asarray(mm) != exact).sum()),
         "matmul_max_abs_err": float(np.abs(np.asarray(mm) - exact).max()),
         "signed_sums_differ": int((np.asarray(ss) != exact).sum())}
    log(f"matmul: default-precision matmul differs from the exact sums in "
        f"{r['matmul_differs']} of {r['values']} values (max abs error "
        f"{r['matmul_max_abs_err']:.3e}); signed sums differ in "
        f"{r['signed_sums_differ']}")
    return r


# -- flatten -----------------------------------------------------------------
def _decode(frames):
    return jax.vmap(lambda fr: decode_frame(fr, STD_K7, SPEC_A))(frames)


FORMS = {
    "in_jit": jax.jit(lambda fr: _decode(fr).reshape(-1)),
    "barrier": jax.jit(
        lambda fr: jax.lax.optimization_barrier(_decode(fr)).reshape(-1)),
}


def flatten_witness(key, frames: int, host_check: bool) -> dict:
    n = frames * SPEC_A.f
    bits, llr = _channel(key, n, EBN0_DB, "1/2", STD_K7)
    fr = jax.jit(lambda x: frame_llr(x, SPEC_A))(llr)
    base_dev = jax.jit(_decode)(fr)
    base = np.asarray(base_dev).reshape(-1)
    sent = np.asarray(bits)
    got = {name: fn(fr) for name, fn in FORMS.items()}
    got["second"] = jax.jit(lambda b: b.reshape(-1))(base_dev)
    cfg = DecoderConfig(spec=SPEC_A, backend="reference")
    got["decoder"] = make_decoder(cfg)(llr, n)
    window = jnp.pad(llr, ((SPEC_A.v1, SPEC_A.v2), (0, 0)))
    got["window"] = PLAN_CACHE.window_decoder(cfg, frames)(window)
    r = {"frames": frames, "ber_frames": float(np.mean(base != sent))}
    for name, out in got.items():
        out = np.asarray(out)[:n]
        r[f"{name}_differs"] = int((out != base).sum())
        r[f"ber_{name}"] = float(np.mean(out != sent))
    if host_check:
        host = jax.devices("cpu")[0]
        with jax.default_device(host):
            ref = np.asarray(jax.jit(_decode)(jax.device_put(
                np.asarray(fr), host))).reshape(-1)
        r["frames_vs_host_differs"] = int((ref != base).sum())
    log(f"flatten F={frames}: " + ", ".join(
        f"{k}={v:.3e}" if isinstance(v, float) else f"{k}={v}"
        for k, v in r.items() if k != "frames"))
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--frames", default=",".join(map(str, SWEEP)),
                    help="comma-separated frame counts of the flatten sweep")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_witness: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    log(f"jax {jax.__version__}; device {dev.device_kind}")
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    am = argmax_witness(jax.random.fold_in(key, 0))
    mm = matmul_witness(jax.random.fold_in(key, 1))
    sweep = [int(s) for s in args.frames.split(",")]
    host_at = 8192 if 8192 in sweep else max(sweep)
    fl = [flatten_witness(jax.random.fold_in(key, 2 + i), F,
                          host_check=F == host_at)
          for i, F in enumerate(sweep)]
    exact = (am["first_zero_differs"] == 0 and mm["signed_sums_differ"] == 0
             and all(r["decoder_differs"] == 0 and r["window_differs"] == 0
                     and r.get("frames_vs_host_differs", 0) == 0
                     for r in fl))
    log(f"witnesses took {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": exact, "device": dev.device_kind, "argmax": am,
                      "matmul": mm, "flatten": fl}), flush=True)
    return 0 if exact else 1


if __name__ == "__main__":
    sys.exit(main())
