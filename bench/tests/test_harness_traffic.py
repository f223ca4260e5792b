"""The traffic generator: its due times and link order, and the data it
makes, are fixed by the seed; every seed offers the same mix."""
import os
import sys

# the benchmark's own modules (``harness``, ``run``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                                          # noqa: E402
import pytest                                               # noqa: E402

from harness import channel, drive, spec                    # noqa: E402
from harness.traffic import Schedule, Traffic               # noqa: E402

CALLS = dict(loop="open", links=8, push_frames=1, pool_pushes=4,
             chunk_frames=1, slots=4, warm_s=0.5)


def _gsm():
    return spec.load_config("gsm_tchfs")


def test_open_schedule_is_fixed_by_the_seed():
    t, cfg = Traffic.from_json(CALLS), _gsm()
    assert t.period_s(cfg) == pytest.approx(0.020)    # one block per 20 ms
    a, b = Schedule(t, cfg, 5, 10.0), Schedule(t, cfg, 5, 10.0)
    c = Schedule(t, cfg, 2 ** 40 + 5, 10.0)
    n = 3 * t.links
    assert [(a.due(i), a.link(i)) for i in range(n)] == \
        [(b.due(i), b.link(i)) for i in range(n)]
    assert [a.link(i) for i in range(n)] != [c.link(i) for i in range(n)]


def test_open_schedule_due_times():
    """Phases evenly spaced over one period, the same set for every
    seed; each link pushes once a period, in due order."""
    t, cfg = Traffic.from_json(CALLS), _gsm()
    T, n = t.period_s(cfg), t.links
    for seed in (0, 7, 2 ** 33 + 1):
        s = Schedule(t, cfg, seed, 1.0)
        due = np.array([s.due(i) for i in range(3 * n)])
        assert np.all(np.diff(due) > 0)
        np.testing.assert_allclose(due[:n] - 1.0,
                                   (np.arange(n) + 0.5) / n * T)
        np.testing.assert_allclose(due[n:] - due[:-n], T)
        links = [s.link(i) for i in range(3 * n)]
        assert sorted(links[:n]) == list(range(n))
        assert links[n:2 * n] == links[:n]


def test_traffic_rejects_bad_mixes():
    with pytest.raises(ValueError):
        Traffic.from_json({**CALLS, "loop": "burst"})
    with pytest.raises(ValueError):
        Traffic.from_json({**CALLS, "links": 0})
    with pytest.raises(TypeError):
        Traffic.from_json({**CALLS, "rate": 3})


def test_pool_is_fixed_by_the_seed():
    cfg = spec.load_config("dvbs_r34")
    a = channel.make_pool(3, cfg, 2, 2, 288)
    b = channel.make_pool(3, cfg, 2, 2, 288)
    c = channel.make_pool(2 ** 40 + 3, cfg, 2, 2, 288)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])         # all 64 seed bits count
    assert a[0].shape == (2, 576) and a[1].shape == (2, 576 * 4 // 3)


def test_every_seed_sends_the_same_channel_mix():
    cfg = _gsm()
    levels = sorted(cfg["ebn0_db"])
    for seed in (1, 2, 2 ** 35):
        got = channel.push_levels(seed, cfg, 3, 8)
        for row in got:
            assert sorted(row) == sorted(levels * 2)
    assert not np.array_equal(channel.push_levels(1, cfg, 3, 8),
                              channel.push_levels(2, cfg, 3, 8))


def _offer_all(seed, polls, cap, wb):
    rec = drive.Recorder(seed, cap * wb, wb)
    for i in range(polls):
        # one poll of link i % 3 carrying two windows
        rec.offer(i % 3, 2 * wb * i, np.full(2 * wb, i % 2, np.int32))
    return rec.sample()


def test_recorder_sample_is_fixed_by_the_seed_and_uniform():
    """The reservoir keeps whole windows with their link and stream
    offset, the same ones for a seed, spread over everything offered."""
    cap, wb, polls = 64, 5, 2000
    link, start, bits = _offer_all(9, polls, cap, wb)
    again = _offer_all(9, polls, cap, wb)
    other = _offer_all(2 ** 40 + 9, polls, cap, wb)
    assert link.shape == (cap,) and bits.shape == (cap, wb)
    for x, y in zip((link, start, bits), again):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(start, other[1])
    window = start // wb                           # 0 .. 2 * polls - 1
    assert len(set(window.tolist())) == cap
    np.testing.assert_array_equal(link, (window // 2) % 3)
    np.testing.assert_array_equal(bits, np.repeat(
        ((window // 2) % 2)[:, None], wb, axis=1))
    # kept windows come from the whole stream, not its head
    assert window.max() > polls and np.median(window) > polls / 2


def test_recorder_keeps_everything_under_its_cap():
    link, start, bits = _offer_all(1, 10, 64, 4)
    np.testing.assert_array_equal(start, np.arange(20) * 4)
