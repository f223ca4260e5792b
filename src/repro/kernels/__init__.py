"""Pallas TPU kernels for the paper's compute hot-spot: compiled by Mosaic
on a TPU (sublane layout), interpreted elsewhere, and checked bit-exact
against ``ref`` in interpret mode; ``packing.Layout`` describes the two
layouts.

Submodules (``ops``, ``ref``, ``autotune``, ``packing``, ...) are imported
on first use rather than eagerly: ``core.traceback`` consumes the layout
vocabulary of ``kernels.packing``, and an eager ``from . import ops`` here
would re-enter ``repro.core`` mid-import — kernels.packing depends on
nothing, everything above it may depend on it. Attribute access
(``repro.kernels.ops``) and ``from repro.kernels import ops`` both work;
the module __getattr__ below resolves them on demand.
"""
import importlib

_SUBMODULES = ("acs", "autotune", "block", "ops", "packing", "ref", "tables",
               "tunedb", "viterbi_fwd", "viterbi_unified")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
