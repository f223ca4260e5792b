"""The one traffic generator. A traffic mix is a data file under
``bench/traffic/`` that sets its parameters; this module reads it with
the configuration, the seed and a clock.

Parameters of a mix:

  loop          "closed": every link pushes its next chunk as soon as the
                server takes it (``Backpressure`` is the flow control);
                "open": link l's push i is due at start + phase_l + i * T,
                T = push bits / the configuration's link rate, whether or
                not earlier pushes have been served.
  links         links (sessions) that stay up for the whole run.
  push_frames   frames of f trellis stages in one push.
  pool_pushes   pushes in each link's pool, pushed cyclically.
  chunk_frames  frames in one decode window (``open_session``).
  slots         ``DecodeServer(slots=...)``: windows batched per launch.
  mesh          devices the server's frame mesh spans (1: no mesh).
  warm_s        open loop: seconds of steady traffic before the window.

Open-loop phases are evenly spaced over one period and dealt to the
links in an order drawn from the seed, so every seed offers the same
arrivals in a different order."""
from __future__ import annotations

import dataclasses

import numpy as np

from . import codes


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    links: int
    push_frames: int
    pool_pushes: int
    chunk_frames: int
    slots: int
    mesh: int = 1
    warm_s: float = 0.0

    @classmethod
    def from_json(cls, d: dict) -> "Traffic":
        t = cls(**d)
        if t.loop not in ("closed", "open"):
            raise ValueError(f"loop must be 'closed' or 'open', got {t.loop!r}")
        if min(t.links, t.push_frames, t.pool_pushes, t.chunk_frames,
               t.slots, t.mesh) < 1:
            raise ValueError(f"traffic sizes must be positive: {d}")
        return t

    def push_stages(self, cfg: dict) -> int:
        return self.push_frames * int(cfg["frame"]["f"])

    def push_symbols(self, cfg: dict) -> int:
        return codes.symbols_per_stages(cfg, self.push_stages(cfg))

    def pool_stages(self, cfg: dict) -> int:
        return self.pool_pushes * self.push_stages(cfg)

    def window_bits(self, cfg: dict) -> int:
        return self.chunk_frames * int(cfg["frame"]["f"])

    def period_s(self, cfg: dict) -> float:
        """Open loop: seconds between two pushes of one link."""
        return self.push_stages(cfg) / float(cfg["link_bps"])


class Schedule:
    """Open-loop due times: push i of the run goes to link ``link(i)`` at
    ``due(i)`` seconds on the caller's clock."""

    def __init__(self, traffic: Traffic, cfg: dict, seed: int,
                 start: float):
        n = traffic.links
        self.period = traffic.period_s(cfg)
        order = np.random.default_rng([int(seed) % (1 << 63), 7]) \
            .permutation(n)
        phases = np.empty(n)
        phases[order] = (np.arange(n) + 0.5) / n * self.period
        self._by_phase = np.argsort(phases, kind="stable")
        self._phase = phases[self._by_phase]
        self.start = float(start)
        self.n = n

    def link(self, i: int) -> int:
        return int(self._by_phase[i % self.n])

    def due(self, i: int) -> float:
        return (self.start + (i // self.n) * self.period
                + float(self._phase[i % self.n]))
