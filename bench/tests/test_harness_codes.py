"""The benchmark's encoders against plain encoders written from the
standards' generator equations and puncturing masks, and the reference
decoder on noise-free streams."""
import os
import sys

# the benchmark's own modules (``harness``, ``run``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                                          # noqa: E402

from harness import channel, codes, spec                    # noqa: E402


def gsm_encode(u):
    """3GPP TS 45.003 3.1.3.1: c(2k) = u(k) + u(k-3) + u(k-4),
    c(2k+1) = u(k) + u(k-1) + u(k-3) + u(k-4), from state 0."""
    u = np.concatenate([np.zeros(4, np.int64), u])
    out = []
    for k in range(4, len(u)):
        out += [u[k] ^ u[k - 3] ^ u[k - 4],
                u[k] ^ u[k - 1] ^ u[k - 3] ^ u[k - 4]]
    return np.array(out)


def dvbs_encode(u):
    """ETSI EN 300 421 4.4.3: G1 = 171 octal (X) = 1+D+D2+D3+D6, G2 = 133
    octal (Y) = 1+D2+D3+D5+D6, tail-biting (the register starts with the
    block's last six bits), punctured to rate 3/4 with X: 1 0 1,
    Y: 1 1 0, sent per period as Y1 X1 Y2 X3."""
    n = len(u)
    d = lambda j, k: u[(k - j) % n]                       # noqa: E731
    out = []
    for k in range(n):
        x = d(0, k) ^ d(1, k) ^ d(2, k) ^ d(3, k) ^ d(6, k)
        y = d(0, k) ^ d(2, k) ^ d(3, k) ^ d(5, k) ^ d(6, k)
        phase = k % 3
        if phase == 0:
            out += [y, x]
        elif phase == 1:
            out += [y]
        else:
            out += [x]
    return np.array(out)


def _noise_free(name, links, pushes, stages):
    cfg = dict(spec.load_config(name), ebn0_db=[300.0])
    bits, rx = channel.make_pool(9, cfg, links, pushes, stages)
    return cfg, bits.astype(np.int64), (rx < 0).astype(np.int64), rx


def test_gsm_encoder_matches_the_standard():
    cfg, bits, coded, _ = _noise_free("gsm_tchfs", 2, 3, 189)
    for link in range(2):
        blocks = bits[link].reshape(3, 189)
        assert np.all(blocks[:, -4:] == 0)                # tail bits
        want = np.concatenate([gsm_encode(b) for b in blocks])
        np.testing.assert_array_equal(coded[link], want)


def test_dvbs_encoder_matches_the_standard():
    cfg, bits, coded, _ = _noise_free("dvbs_r34", 2, 2, 288)
    assert codes.code_rate(cfg) == 0.75
    for link in range(2):
        np.testing.assert_array_equal(coded[link], dvbs_encode(bits[link]))


def test_reference_decodes_noise_free_streams():
    for name in ("gsm_tchfs", "dvbs_r34"):
        cfg, bits, _, rx = _noise_free(name, 2, 4, cfg_f(name))
        k, polys = codes.generators(cfg)
        ref = spec.reference(cfg["reference"])
        stages = bits.shape[1]
        first, steady = ref.expected(rx, k, polys, codes.puncture_mask(cfg),
                                     cfg["frame"], stages)
        np.testing.assert_array_equal(steady, bits)
        np.testing.assert_array_equal(first, bits[:, :cfg["frame"]["f"]])


def cfg_f(name):
    return spec.load_config(name)["frame"]["f"]


def test_tap_lists():
    assert codes.taps(5, 0o23) == [0, 3, 4]
    assert codes.taps(5, 0o33) == [0, 1, 3, 4]
    assert codes.taps(7, 0o171) == [0, 1, 2, 3, 6]
    assert codes.taps(7, 0o133) == [0, 2, 3, 5, 6]
