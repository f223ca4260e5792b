"""Compiled-mode benchmark support: platform selection.

Every trajectory point recorded so far is interpret-mode on a shared CPU;
the paper's regime is compiled kernels on the chip. This module is the
switch between the two worlds: it configures JAX for whatever backend the
machine has and reports whether a TPU actually exists, so
``throughput.py --compiled`` and ``bench_gate.py`` (``BENCH_COMPILED=1``)
can no-op — exit 0 with a clear notice — on CPU-only runners instead of
recording a "compiled" point that is really the interpreter.

Compiled runs need no schema of their own: every trajectory run is
stamped with ``trajectory.platform()`` (backend + device kind +
jax_version) and the regression gate only compares same-platform runs,
so a TPU trajectory and the interpret-CPU trajectory live side by side
in one BENCH_kernels.json and gate independently.
"""
from __future__ import annotations

__all__ = ["set_platform", "accelerator"]


def set_platform(platform: str | None = None) -> str:
    """Configure JAX for compiled benchmarking and return the backend
    that is actually in effect. ``platform`` forces a backend (``'tpu'``/
    ``'cpu'``, e.g. from ``BENCH_PLATFORM``); None lets JAX pick its
    default — the TPU when one exists, else CPU."""
    import jax
    if platform:
        jax.config.update("jax_platform_name", platform)
    return jax.default_backend()


def accelerator() -> str | None:
    """The real-hardware backend name (``'tpu'``), or None when only CPU
    is available — the "should compiled mode run at all?" predicate."""
    import jax
    backend = jax.default_backend()
    return None if backend == "cpu" else backend
