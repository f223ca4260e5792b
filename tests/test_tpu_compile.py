"""Ahead-of-time compiles of the chip's decode programs for a described TPU
v5e (no chip attached): what Mosaic refuses here, it would refuse on the
chip. Covers the programs chip_smoke.py runs — the unified kernel at
phase A's shape, phase B's serve-bucket batch decoders at their padded
batch, phase C's blocked and sequential long-frame specs — plus the
non-default kernel knobs that compile (radix 2, unpacked survivors), and
phase D's frame-sharded batch decoder on the described 2x2 host; and the
benchmark's launches that chip_smoke.py does not make: the K=9 rate-1/3
UMTS bucket (256 states, three generators) at its 1,024-frame launch and
the DVB-S bucket's 4,096-frame launch sharded over the 2x2 host.

The topology is described inside a fixture, never while a module is
imported (only one process may load the TPU library at a time), and the
persistent compilation cache is off: a compile for a described chip can
be written to it but not read back."""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import DecoderConfig, FrameSpec, STD_K7, make_trellis
from repro.kernels import ops
from repro.kernels.block import resolve_block
from repro.serve import PlanCache

#: v5e HBM per chip
HBM_BYTES = 16 * 1024 ** 3

SPEC_A = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
SPEC_12 = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
SPEC_34 = FrameSpec(f=63, v1=21, v2=21, f0=21, v2s=21)     # L=105, odd
SPEC_C = FrameSpec(f=4096, v1=32, v2=32, f0=32, v2s=32)
SPEC_UMTS = FrameSpec(f=268, v1=36, v2=54, f0=67, v2s=54)
SPEC_DVBS = FrameSpec(f=288, v1=21, v2=45, f0=32, v2s=45)
K5 = (5, (0o23, 0o35))
K7 = (7, (0o171, 0o133))
K9 = (9, (0o557, 0o663, 0o711))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library's logs would otherwise go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()      # the Pallas kernel
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < HBM_BYTES
    return compiled


@pytest.mark.parametrize("frames,knobs", [
    (8192, {}),                                   # phase A, default knobs
    (1024, dict(radix=2)),
    (1024, dict(pack_survivors=False)),
])
def test_unified_kernel_phase_a(one_chip, frames, knobs):
    fn = lambda fr: ops.viterbi_decode_frames(      # noqa: E731
        fr, STD_K7, SPEC_A, interpret=False, **knobs)
    _compile(fn, (frames, SPEC_A.frame_len, 2), one_chip)


@pytest.mark.parametrize("code,spec,frames", [
    (K7, SPEC_12, 16 * 16), (K7, SPEC_34, 16 * 16), (K5, SPEC_12, 16 * 16),
    (K9, SPEC_UMTS, 8 * 128)],
    ids=["K7-f64", "K7-f63-oddL", "K5-f64", "K9-r13-f268"])
def test_serve_bucket_batch_decoder(one_chip, code, spec, frames):
    """The jitted program one serve bucket launches: slots=16 windows of
    16 frames, as phase B batches them; the UMTS cell's 8 windows of 128
    blocks of the K=9 rate-1/3 code."""
    trellis = make_trellis(*code)
    cfg = DecoderConfig(trellis=trellis, spec=spec, backend="kernel",
                        interpret=False)
    fn = PlanCache().batch_decoder(cfg, frames)
    _compile(fn, (frames, spec.frame_len, trellis.beta), one_chip)


@pytest.mark.parametrize("blocked", [True, False],
                         ids=["blocked", "sequential"])
def test_long_frame_phase_c(one_chip, blocked):
    bf, ov = resolve_block(STD_K7, SPEC_C, "auto" if blocked else 1, None)
    assert (bf > 1) is blocked
    fn = lambda fr: ops.viterbi_decode_frames(      # noqa: E731
        fr, STD_K7, SPEC_C, interpret=False,
        block_frames=bf, overlap=ov)
    _compile(fn, (8, SPEC_C.frame_len, 2), one_chip)


@pytest.mark.parametrize("spec,frames", [(SPEC_12, 16 * 16),
                                         (SPEC_DVBS, 32 * 128)],
                         ids=["phase-d", "dvbs-bulk32"])
def test_sharded_serve_batch_four_chips(topo, spec, frames):
    """Phase D's program, and the DVB-S four-chip cell's 32 windows of
    128 frames: a serve bucket's batch decoder on a 4-chip 'frames' mesh
    keeps the batch sharded over all four chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("frames",))
    cfg = DecoderConfig(spec=spec, backend="kernel", interpret=False)
    fn = PlanCache().batch_decoder(cfg, frames, mesh=mesh)
    compiled = _compile(fn, (frames, spec.frame_len, 2),
                        NamedSharding(mesh, P("frames")))
    assert compiled.output_shardings.spec == P("frames")
    assert compiled.output_shardings.mesh.devices.size == 4
