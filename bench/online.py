"""Drive the program's ``MicroBatcher`` on the wall clock.

``MicroBatcher.run`` replays a trace on a virtual clock that advances only
by engine time. This adapter keeps its scheduling policy and changes two
things: no dispatch starts before its due wall time (the batcher's virtual
``now`` plus the window's start), and after each dispatch the clock is the
wall clock, so host padding, result copies and unpacking count. Latency is
then from each request's due time to the wall time its answer was unpacked.
"""

from __future__ import annotations

import time

import jax

from repro.serving import MicroBatcher
from repro.core.engine import snap_to_bucket


class GiveUp(Exception):
    """A dispatch would start past the deadline: what is still queued is
    counted as never answered."""


class WallClockBatcher(MicroBatcher):
    def __init__(self, session, *, t0: float, give_up_s: float, **kw):
        super().__init__(session, **kw)
        self.t0 = t0
        self.give_up_s = give_up_s
        self.dispatches = []  # dicts: due, start, end (s from t0), rows, bucket
        self.done = None

    def _dispatch(self, batch, now, done):
        self.done = done
        wait = self.t0 + now - time.perf_counter()
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.pace"):
                time.sleep(wait)
        start = time.perf_counter() - self.t0
        if start > self.give_up_s:
            raise GiveUp
        n0 = len(done)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            super()._dispatch(batch, now, done)
        end = time.perf_counter() - self.t0
        for c in done[n0:]:
            c.finish = end
            c.wait_ms = (start - c.arrival) * 1e3
            c.compute_ms = (end - start) * 1e3
        rows = sum(r.rows for r in batch)
        self.dispatches.append({
            "due": now, "start": start, "end": end, "rows": rows,
            "bucket": snap_to_bucket(rows, self.session.buckets),
            "rids": [r.rid for r in batch],
        })
        return end
