"""Rehearsal of chip_smoke.py on the CPU: every phase at a tiny size with
the Pallas kernels interpreted, including its zero-fault and bit-identity
checks; phase D on four virtual CPU devices in this process. ``main()``
itself must refuse to run without a TPU."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke                                        # noqa: E402
from repro.compile_cache import CHECKOUT_CACHE, use_compile_cache  # noqa: E402

#: the chip's kernel path, interpreted: what DecoderConfig resolves to on
#: a TPU, with interpret mode on
OVER = dict(backend="kernel", layout="sublane", interpret=True)


def _key(i):
    return jax.random.fold_in(jax.random.PRNGKey(0), i)


def test_rehearse_phase_a_single_shot():
    r = chip_smoke.phase_a(_key(0), frames=8, over=OVER)
    assert r["bits"] == 8 * 256 and r["ber"] <= 1e-3


def test_rehearse_phase_b_serve_mix():
    bits = chip_smoke.phase_b(_key(1), sessions=8, rounds=2, chunk=2,
                              slots=4, over=OVER)
    assert [len(b) for b in bits] == [256] * 4 + [252] * 2 + [256] * 2


def test_rehearse_phase_c_long_frame():
    r = chip_smoke.phase_c(_key(2), frames=2, chunk=1, over=OVER)
    assert abs(r["ber_blocked"] - r["ber_sequential"]) <= 1e-3


@pytest.fixture()
def four_cpu_devices():
    """Four virtual CPU devices in this process: the CPU client is rebuilt
    with four devices, and with the old count after the test."""
    from jax.extend.backend import clear_backends
    old = jax.config.jax_num_cpu_devices
    clear_backends()
    jax.config.update("jax_num_cpu_devices", 4)
    try:
        yield jax.devices()
    finally:
        clear_backends()
        jax.config.update("jax_num_cpu_devices", old)


def test_rehearse_phase_d_four_devices(four_cpu_devices):
    from repro.distributed.stream import frame_mesh
    assert len(four_cpu_devices) == 4
    chip_smoke.phase_d(_key(3), frame_mesh(four_cpu_devices), sessions=8,
                       rounds=2, chunk=2, slots=4, over=OVER)


def test_smoke_checks_fail_loudly():
    """A phase whose config does not select the sublane kernel, a kernel
    traced in interpret mode, or a phase in which no kernel compiled is a
    failure, not a pass."""
    with pytest.raises(chip_smoke.SmokeFailure, match="sublane kernel"):
        chip_smoke.kernel_cfg({"backend": "reference"},
                              spec=chip_smoke.SPEC_12)
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        with chip_smoke.Traced(interpret=False) as tr:
            tr.tracer.event("kernel_trace", interpret=True)
        tr.kernel_plans()
    with pytest.raises(chip_smoke.SmokeFailure, match="no kernel_trace"):
        with chip_smoke.Traced(interpret=False) as tr:
            pass                             # e.g. a program traced before
        tr.kernel_plans()


def test_main_refuses_without_tpu(monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(chip_smoke, "use_compile_cache",
                        lambda: calls.append(1) or "unused")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert calls == [1]                      # the cache is set up first
    assert out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_location(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX and no other
    directory is set; otherwise the cache goes to <checkout>/.jax_cache."""
    old = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    if env is None:
        assert got == now == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE.name == ".jax_cache"
        assert (CHECKOUT_CACHE.parent / "chip_smoke.py").exists()
    else:
        assert got == env and now == old
