"""The loop that drives the server through a measured window.

It is the program's ``serving/frontend.py`` ``ReplayDriver`` loop
(pump due arrivals -> start a wave or dispatch a cycle -> pump -> admit
into idle slots -> complete the cycle), with three additions:

* it runs to a fixed window end, not until the trace drains;
* it stamps each request's tokens when they reach the host: after
  ``complete_cycle`` (the cycle's tokens) and after ``dispatch_cycle``
  (which first reads back the anchors of earlier installs);
* it wraps each call into a layer in a ``TraceAnnotation`` span named
  ``bench.<call>``, so a device trace shows what the host was doing in
  each idle gap.

:class:`PinnedEngine` sizes every wave for the largest request the cell
can send, so the decode and install programs keep one shape (a table
width and cache length) for the whole run and nothing compiles in the
window. The program sizes a wave from the requests visible when it
starts; the pin adds a sizing-only candidate of the cell's largest
shape to that view, and installs nothing for it. It also installs one
request per call: the program groups every same-bucket admission into
one call, whose memory grows with the group's padded tokens and whose
group size is a program shape of its own, so the first batch of long
steady-state prompts would exhaust the chip's memory, and two rows
freed in one cycle would compile in the window.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.serving.engine import Request, ServingEngine


def span(name: str):
    return jax.profiler.TraceAnnotation("bench." + name)


class PinnedEngine(ServingEngine):
    """``ServingEngine`` whose waves are all sized for one largest request
    (``largest`` = (prompt length, max_new)), and whose install calls hold
    one request each."""

    def __init__(self, *args, largest=(1, 1), **kw):
        super().__init__(*args, **kw)
        self._sizing = Request(-1, np.zeros((largest[0],), np.int32),
                               largest[1])

    def _install_batch(self, grp, pad, warm=False):
        for slot, r, pfx in grp:
            self._install(slot, r, prefix_len=pfx)

    def _next_wave(self):
        take = super()._next_wave()
        self.queue.insert(0, self._sizing)
        return take

    def start_wave(self, width=None):
        try:
            return super().start_wave(width)
        finally:
            if self._sizing in self.queue:
                self.queue.remove(self._sizing)


@dataclasses.dataclass
class Track:
    """One request as the host sees it."""
    req: object                 # traffic.Req
    due: float                  # perf_counter time it was due
    uid: int = -1               # the engine's uid once submitted
    n: int = 0                  # tokens read back so far
    n_open: int = 0             # ... when the window opened
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    done: bool = False
    out: Optional[np.ndarray] = None


@dataclasses.dataclass
class Cycle:
    t0: float
    t1: float
    rows: int
    lens: List[int]             # cache length of each active row
    n_out: List[int]            # tokens committed by each active row
    pool_use: float             # share of the page pool in use


class Driver:
    def __init__(self, eng: ServingEngine, reqs, t_start: float):
        """``reqs`` (``traffic.Req``) are due at ``t_start + req.due``."""
        self.eng = eng
        self.tracks = [Track(r, t_start + r.due) for r in reqs]
        self.by_uid: Dict[int, Track] = {}
        self._next = 0
        self._done_seen = len(eng.done)
        self.cycles: List[Cycle] = []

    # ---------------------------------------------------------- events --
    def pump(self, now: float) -> None:
        with span("pump"):
            while (self._next < len(self.tracks)
                   and self.tracks[self._next].due <= now):
                tr = self.tracks[self._next]
                tr.uid = self.eng.submit(tr.req.prompt, tr.req.max_new)
                self.by_uid[tr.uid] = tr
                self._next += 1

    def observe(self) -> None:
        """Stamp tokens that have reached the host."""
        now = time.perf_counter()
        w = self.eng.wave
        if w is not None:
            for slot, r in enumerate(w.requests):
                if r is None or slot in w.pending_anchor:
                    continue
                self._seen(self.by_uid[r.uid],
                           min(int(w.filled[slot]), r.max_new), now)
        done = self.eng.done
        for r in done[self._done_seen:]:
            tr = self.by_uid[r.uid]
            self._seen(tr, r.max_new, now)
            tr.done, tr.out = True, r.out
        self._done_seen = len(done)

    @staticmethod
    def _seen(tr: Track, n: int, now: float) -> None:
        if n > tr.n:
            if tr.t_first is None:
                tr.t_first = now
            tr.t_last = now
            tr.n = n

    # ------------------------------------------------------------ loop --
    def step(self) -> None:
        """One iteration of the serving loop."""
        eng = self.eng
        now = time.perf_counter()
        self.pump(now)
        if eng.wave is None:
            if eng.queue:
                with span("start_wave"):
                    eng.start_wave(width=eng.batch_size)
            return
        w = eng.wave
        t0 = time.perf_counter()
        with span("dispatch_cycle"):
            handle = eng.dispatch_cycle()
        active = handle[0]
        lens = [len(w.requests[i].prompt) + int(w.filled[i]) - 1
                for i in np.flatnonzero(active)]
        pool_use = w.pool.pages_in_use / w.pool.n_pages
        self.observe()
        self.pump(time.perf_counter())
        with span("admit_idle"):
            eng.admit_idle()
        with span("complete_cycle"):
            eng.complete_cycle(handle)
        t1 = time.perf_counter()
        n_out = np.asarray(handle[1]["n_out"])[active]
        self.cycles.append(Cycle(t0, t1, len(active), lens,
                                 [int(x) for x in n_out], pool_use))
        self.observe()

    def run_until(self, t_end: float) -> None:
        while time.perf_counter() < t_end:
            self.step()

    def open_window(self) -> int:
        """Mark the window's start; returns the index of its first cycle."""
        for tr in self.tracks:
            tr.n_open = tr.n
        return len(self.cycles)

    def tokens_since_open(self) -> int:
        return sum(tr.n - tr.n_open for tr in self.tracks)

    def served(self):
        """(track, tokens it has been served) of every request that has
        tokens on the host: finished ones whole, and the rows still
        running up to their last committed token."""
        out = [(tr, np.asarray(tr.out[: tr.req.max_new]))
               for tr in self.tracks if tr.done]
        w = self.eng.wave
        if w is not None:
            for slot, r in enumerate(w.requests):
                if r is None or slot in w.pending_anchor:
                    continue
                tr = self.by_uid[r.uid]
                out.append((tr, w.bufs[slot, : tr.n].copy()))
        return out
