"""Offline batch as a closed loop.

``outstanding_per_slot * slots`` requests are always outstanding; each
completion releases the next.  Request ``i`` of ``requests`` has a prompt
and an output length drawn (lognormal, clipped) from the mix's
``shape_seed``, in that order for every seed.  Before the window the
traffic runs until every slot holds a request; the window answers for
the requests that end inside it.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.generator import Pending, Traffic, lognormal_lengths


class Generator(Traffic):

    def __init__(self, mix: dict, **kw):
        super().__init__(mix, **kw)
        shape = np.random.default_rng(mix["shape_seed"])
        n = int(mix["requests"])
        pl = lognormal_lengths(shape, n, mix["prompt"])
        ol = lognormal_lengths(shape, n, mix["output"])
        self.jobs = list(zip(pl.tolist(), ol.tolist()))
        self.outstanding = int(mix["outstanding_per_slot"] * self.slots)
        self._next_job = 0
        self._open = 0

    def poll(self, now: float, finished=()) -> List[Pending]:
        out: List[Pending] = []
        self._open -= len(finished)
        while self._open < self.outstanding:
            if self._next_job >= len(self.jobs):
                raise RuntimeError("closed-loop mix ran out of requests; "
                                   "raise its 'requests'")
            pl, ol = self.jobs[self._next_job]
            self._next_job += 1
            out.append(Pending(self.request(self.tokens(pl), ol), now))
            self._open += 1
        return out

    def prefill_bounds(self):
        return int(self.mix["prompt"]["min"]), int(self.mix["prompt"]["max"])

    def before_window(self, drv) -> None:
        drv.poll()
        drv.step()
        while any(r is None for r in drv.eng.slot_req):
            drv.turn()

    def counted(self, drv, t_open, t_close):
        return [(p, drv.t0 + p.due) for p, seen in drv.done
                if t_open <= seen <= t_close]
