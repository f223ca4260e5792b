"""The client side of a run: links that push their pools into the
server, the closed and open loops, and what the benchmark's own clock
records. A window's latency runs from the due time of the push that
completed it to the return of the ``poll`` that handed its bits back.

With ``annotate`` set (a traced run) the loop's pushes, steps, polls
and waits run inside ``jax.profiler.TraceAnnotation`` spans, so the
device's idle gaps can be put against what the host was doing."""
from __future__ import annotations

import array
import collections
import contextlib
import gc
import math
import time

import numpy as np

clock = time.perf_counter


class Link:
    """One session's client: its pool of pushes, the windows its pushes
    have completed, and the bits it has polled back."""

    def __init__(self, index: int, sid: int, pushes: list, push_stages: int,
                 window_bits: int, v2: int):
        self.index = index
        self.sid = sid
        self.pushes = pushes
        self.push_stages = push_stages
        self.window_bits = window_bits
        self.v2 = v2
        self.n_push = 0
        self.stages = 0
        self.windows = 0
        self.pending = collections.deque()   # (due, counted) per window
        self.received = 0                    # bits polled so far

    def next_push(self) -> np.ndarray:
        return self.pushes[self.n_push % len(self.pushes)]

    def pushed(self, due: float, counted: bool) -> int:
        """Book one accepted push; returns the windows it completed (a
        window needs v2 stages beyond its chunk)."""
        self.n_push += 1
        self.stages += self.push_stages
        total = max(0, (self.stages - self.v2) // self.window_bits)
        new = total - self.windows
        self.windows = total
        self.pending.extend([(due, counted)] * new)
        return new


class Recorder:
    """What the benchmark's clock and bookkeeping saw, split by whether
    the event falls in the measured window. Keeps a reservoir sample,
    drawn from the seed, of the windows polled from the window on, for
    the comparison with the reference.

    Its records are flat arrays allocated up front, not Python containers
    that grow by an object per window, so the client gives Python's
    garbage collector, which would run inside the server's calls, no
    work in the window (``GcWatch`` reports the collections that ran)."""

    def __init__(self, seed: int, sample_bits: int, window_bits: int):
        self.push_s = array.array("d")   # host s per push call, in window
        self.lag_s = array.array("d")    # open loop: push start - due
        self.lat_s = array.array("d")    # per counted window: return - due
        self.bits_in_window = 0
        self.attempted = 0        # windows completed by counted pushes
        self.refused = 0          # pushes refused with Backpressure
        self.anomalies = []       # polls that returned what no push made
        self._rng = np.random.default_rng([int(seed) % (1 << 63), 11])
        self._wb = window_bits
        self._cap = cap = max(1, sample_bits // window_bits)
        self._link = np.zeros(cap, np.int64)
        self._start = np.zeros(cap, np.int64)
        self._bits = np.zeros((cap, window_bits), np.int8)
        self._seen = 0
        # reservoir sampling by skips (Li's algorithm L): the next window
        # offered that replaces a kept one, and the running weight
        self._w = 1.0
        self._next = cap
        self._skip()

    def _skip(self) -> None:
        r = self._rng.random(2)
        self._w *= math.exp(math.log(1.0 - r[0]) / self._cap)
        if self._w < 1.0:
            self._next += int(math.log(1.0 - r[1])
                              / math.log1p(-self._w))

    def offer(self, link: int, start: int, bits: np.ndarray) -> None:
        """Offer each whole window of a poll to the sample."""
        wb = self._wb
        for w in range(bits.size // wb):
            i = self._seen
            self._seen += 1
            if i < self._cap:
                slot = i
            elif i == self._next:
                slot = int(self._rng.integers(0, self._cap))
                self._next += 1
                self._skip()
            else:
                continue
            self._link[slot] = link
            self._start[slot] = start + w * wb
            self._bits[slot] = bits[w * wb:(w + 1) * wb]

    def sample(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(link (m,), first bit offset (m,), bits (m, window bits)) of
        the m windows kept."""
        m = min(self._seen, self._cap)
        return self._link[:m], self._start[:m], self._bits[:m]


class GcWatch:
    """Python's garbage collections while it is installed: the host
    seconds of each and its generation."""

    def __init__(self):
        self.pause_s = array.array("d")
        self.gen = array.array("b")
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = clock()
        else:
            self.pause_s.append(clock() - self._t)
            self.gen.append(info["generation"])

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        pause = np.asarray(self.pause_s) * 1e3
        gen = np.asarray(self.gen)
        return {"collections": [int(np.sum(gen == g)) for g in range(3)],
                "max_ms": float(pause.max()) if pause.size else 0.0,
                "gen2_ms": float(pause[gen == 2].sum())}


def poll(srv, ln: Link, rec: Recorder, w0: float, w1: float) -> None:
    """Collect a link's returned bits and book each returned window; bits
    returned in [w0, w1) count for the window, and polls from w0 on are
    offered to the sample."""
    out = srv.poll(ln.sid)
    now = clock()
    n = int(out.size)
    if not n:
        return
    start = ln.received
    ln.received += n
    if n % ln.window_bits or n // ln.window_bits > len(ln.pending):
        rec.anomalies.append(f"link {ln.index}: poll of {n} bits with "
                             f"{len(ln.pending)} windows pending")
    for _ in range(min(n // ln.window_bits, len(ln.pending))):
        due, counted = ln.pending.popleft()
        if counted:
            rec.lat_s.append(now - due)
    if w0 <= now < w1:
        rec.bits_in_window += n
    if now >= w0:
        rec.offer(ln.index, start, out)


def annotator(trace: bool):
    """``jax.profiler.TraceAnnotation`` in a traced run, else a no-op."""
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def closed_loop(srv, links, busy_exc, rec: Recorder, *, until: float,
                w0: float, w1: float, iterations: int | None = None,
                trace: bool = False) -> None:
    """Every link pushes its next chunk each round (a refused push is
    retried next round), then one ``step`` and a ``poll`` of every link.
    Runs until ``until`` on the clock, or for ``iterations`` rounds."""
    ann = annotator(trace)
    rounds = 0
    while clock() < until and (iterations is None or rounds < iterations):
        rounds += 1
        with ann("bench.push"):
            for ln in links:
                t0 = clock()
                try:
                    srv.push(ln.sid, ln.next_push())
                    ok = True
                except busy_exc:
                    ok = False
                t1 = clock()
                counted = w0 <= t0 < w1
                if counted:
                    rec.push_s.append(t1 - t0)
                if ok:
                    new = ln.pushed(t0, counted)
                    if counted:
                        rec.attempted += new
                else:
                    rec.refused += 1
        with ann("bench.step"):
            srv.step()
        with ann("bench.poll"):
            for ln in links:
                poll(srv, ln, rec, w0, w1)


class OpenLoop:
    """Pushes go out at their due times, whether or not earlier ones are
    served. Each round pushes what is due, at most ``slots`` pushes, then
    runs one ``step`` and a ``poll`` of every link with windows
    outstanding, or waits for the next due time. A push the server
    refuses (``Backpressure``) waits, with the later pushes of its link,
    until the server takes it; other links go on. Pushes due in
    [w0, w1) are the window's."""

    def __init__(self, srv, links, busy_exc, rec: Recorder, sched, *,
                 slots: int, w0: float, w1: float, trace: bool = False):
        self.srv, self.links, self.busy_exc = srv, links, busy_exc
        self.rec, self.sched, self.w0, self.w1 = rec, sched, w0, w1
        self.slots = slots
        self.ann = annotator(trace)
        self.awaiting = set()
        self.deferred = {}          # link -> due times of refused pushes
        self.i = 0

    def _push(self, ln: Link, due: float) -> bool:
        rec = self.rec
        t0 = clock()
        try:
            self.srv.push(ln.sid, ln.next_push())
        except self.busy_exc:
            rec.refused += 1
            return False
        t1 = clock()
        counted = self.w0 <= due < self.w1
        if counted:
            rec.push_s.append(t1 - t0)
            rec.lag_s.append(t0 - due)
        new = ln.pushed(due, counted)
        if counted:
            rec.attempted += new
        if new:
            self.awaiting.add(ln)
        return True

    def _push_due(self, now: float) -> None:
        budget = self.slots
        for ln, dues in list(self.deferred.items()):
            while dues and budget and self._push(ln, dues[0]):
                dues.popleft()
                budget -= 1
            if not dues:
                del self.deferred[ln]
        sched = self.sched
        due = sched.due(self.i)
        while budget and due <= now:
            ln = self.links[sched.link(self.i)]
            if ln in self.deferred:
                self.deferred[ln].append(due)
            elif self._push(ln, due):
                budget -= 1
            else:
                self.deferred[ln] = collections.deque([due])
            self.i += 1
            due = sched.due(self.i)

    def run(self, until: float) -> None:
        """Run the schedule until ``until`` on the clock."""
        self._loop(lambda now: now >= until, lambda now: now, until)

    def catch_up(self, timeout_s: float = 60.0) -> None:
        """After the window: make every push that fell due in it and is
        still outstanding (late, not lost), with no new ones."""
        limit = clock() + timeout_s
        last = self.w1 - 1e-9
        self._loop(lambda now: now >= limit or (
            not self.deferred and self.sched.due(self.i) > last),
            lambda now: last, limit)

    def _loop(self, done, bound, until) -> None:
        srv, rec, ann, awaiting = self.srv, self.rec, self.ann, self.awaiting
        while True:
            now = clock()
            if done(now):
                break
            if self.deferred or self.sched.due(self.i) <= bound(now):
                with ann("bench.push"):
                    self._push_due(bound(now))
            if awaiting:
                with ann("bench.step"):
                    srv.step()
                with ann("bench.poll"):
                    for ln in list(awaiting):
                        poll(srv, ln, rec, self.w0, self.w1)
                        if not ln.pending:
                            awaiting.discard(ln)
            else:
                with ann("bench.wait"):
                    wait = min(self.sched.due(self.i), until) - clock()
                    if wait > 0:
                        time.sleep(wait)


def drain(srv, links, rec: Recorder, timeout_s: float = 60.0,
          sample: bool = True) -> None:
    """No new pushes; step and poll until every completed window has come
    back, or ``timeout_s`` has passed. After the window, what comes back
    is offered to the sample (``sample``)."""
    w0 = float("-inf") if sample else float("inf")
    limit = clock() + timeout_s
    while any(ln.pending for ln in links) and clock() < limit:
        srv.step()
        for ln in links:
            if ln.pending:
                poll(srv, ln, rec, w0, float("-inf"))


def warm_open(srv, links, busy_exc, rec: Recorder, slots: int) -> None:
    """Launch every batch size the open loop can: each link's first push
    (it completes no window), then for b = 1..slots, b pushes round the
    links followed by one ``step``, which launches b windows."""
    for ln in links:
        srv.push(ln.sid, ln.next_push())
        ln.pushed(clock(), False)
    nxt = 0
    for b in range(1, slots + 1):
        for _ in range(b):
            ln = links[nxt % len(links)]
            nxt += 1
            srv.push(ln.sid, ln.next_push())
            ln.pushed(clock(), False)
        srv.step()
    drain(srv, links, rec, sample=False)
