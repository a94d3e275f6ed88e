"""Open-loop chat sessions.

Users start sessions at the times of a Poisson process of rate
``sessions_per_s``.  A session has ``turns.min..turns.max`` turns; each
turn's prompt is the session's system prompt (one of ``system.count``
shared prompts of ``system.length`` tokens, chosen Zipf(``zipf_s``)),
the whole history (earlier messages and the replies the server gave) and
a new message.  The next turn is due a think time (exponential, mean
``think_s``) after the previous reply reached the host.  A session ends
before the turn that would pass ``max_len``.  With ``system.count`` 0 a
session starts from its message alone, so no two prompts share a prefix.

Session ``i`` draws its arrival gap, turns, lengths, think times and
system prompt from its own stream, seeded by (``shape_seed``, i): every
seed and every window length serve the same sessions at the same times.

The window answers for the requests that fall due inside it; before it
the traffic runs for ``preroll_s``, and after it the harness waits up to
``drain_s`` for those requests to finish.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from bench.generator import Pending, Traffic, lognormal_lengths, zipf_choice


class Generator(Traffic):

    def __init__(self, mix: dict, **kw):
        super().__init__(mix, **kw)
        sysp = mix["system"]
        self.system_prompts = [self.tokens(sysp["length"])
                               for _ in range(sysp["count"])]
        self.sessions, self.arrivals, t = [], [], 0.0
        while True:
            s = self._shape(len(self.sessions))
            t += s["gap"] / mix["sessions_per_s"]
            if t > self.horizon_s:
                break
            # every message is drawn now, so its tokens do not depend on
            # the order in which the run's turns come due
            s["tokens"] = [self.tokens(int(s["msg"][j]))
                           for j in range(s["turns"])]
            self.arrivals.append(t)
            self.sessions.append(s)
        self._next_arrival = 0
        self._due: List[Pending] = []

    def _shape(self, i: int) -> dict:
        m = self.mix
        rng = np.random.default_rng([m["shape_seed"], i])
        k = m["turns"]["max"]
        sysp = m["system"]
        return {
            "gap": float(rng.exponential(1.0)),
            "turns": int(rng.integers(m["turns"]["min"], k + 1)),
            "msg": lognormal_lengths(rng, k, m["message"]),
            "out": lognormal_lengths(rng, k, m["output"]),
            "think": rng.exponential(m["think_s"], k),
            "system": int(zipf_choice(rng, 1, sysp["count"],
                                      sysp["zipf_s"])[0])
            if sysp["count"] else None}

    def _turn(self, sid: int, turn: int, history: List[int], due: float):
        s = self.sessions[sid]
        if turn >= s["turns"]:
            return None
        prompt = history + s["tokens"][turn]
        max_new = int(s["out"][turn])
        if len(prompt) + max_new > self.max_len:
            return None
        return Pending(self.request(prompt, max_new), due, sid, turn)

    def poll(self, now: float, finished=()) -> List[Pending]:
        for p, t_seen in finished:
            s = self.sessions[p.session]
            nxt = self._turn(p.session, p.turn + 1,
                             list(p.req.prompt) + list(p.req.output),
                             t_seen + float(s["think"][p.turn]))
            if nxt is not None:
                self._due.append(nxt)
        while (self._next_arrival < len(self.arrivals)
               and self.arrivals[self._next_arrival] <= now):
            sid = self._next_arrival
            self._next_arrival += 1
            which = self.sessions[sid]["system"]
            system = [] if which is None else self.system_prompts[which]
            first = self._turn(sid, 0, list(system), self.arrivals[sid])
            if first is not None:
                self._due.append(first)
        ready = sorted((p for p in self._due if p.due <= now),
                       key=lambda p: p.due)
        self._due = [p for p in self._due if p.due > now]
        return ready

    def next_due(self) -> Optional[float]:
        times = [p.due for p in self._due]
        if self._next_arrival < len(self.arrivals):
            times.append(self.arrivals[self._next_arrival])
        return min(times) if times else None

    def prefill_bounds(self):
        # a turn whose history is cached prefills its message; one whose
        # prefix was evicted prefills whole
        return int(self.mix["message"]["min"]), self.max_len

    def before_window(self, drv) -> None:
        while time.perf_counter() - drv.t0 < float(self.mix["preroll_s"]):
            drv.turn()

    def _in_window(self, drv, t_open, t_close):
        return [(p, d) for p, d in drv.submitted if t_open <= d < t_close]

    def after_window(self, drv, t_open, t_close) -> None:
        t_end = t_close + float(self.mix["drain_s"])
        drv.poll()
        while any(not p.req.terminal
                  for p, _ in self._in_window(drv, t_open, t_close)) \
                and time.perf_counter() < t_end:
            drv.turn()

    def counted(self, drv, t_open, t_close):
        return self._in_window(drv, t_open, t_close)
