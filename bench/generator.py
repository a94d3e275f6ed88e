"""What every traffic generator shares, and the loader that finds a mix's
generator by name.

A traffic mix is a data file ``bench/traffic/<mix>.json``.  Its
``generator`` key names the module ``bench/traffic/<generator>.py`` that
reads it; that module defines ``Generator``, a subclass of ``Traffic``.
A new mix of an existing shape is one new data file; a new shape is one
new generator module beside it.

Every length, count, time and order is drawn from the mix's own
``shape_seed``, so every ``--seed`` serves the same work at the same
times; the run's seed draws only the token ids.  The first token of every
message, system prompt and batch prompt is distinct, so prompts share a
prefix only where the mix says they do.

Times are wall-clock seconds from the start of the traffic (``due`` =
when the request is due to be sent).
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def load(mix: dict, root: Path = ROOT) -> type:
    """The ``Generator`` class of ``bench/traffic/<mix['generator']>.py``."""
    name = mix["generator"]
    path = root / "bench" / "traffic" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no traffic generator {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Generator


def lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    """n integer lengths: round(lognormal(log(median), sigma)), clipped."""
    raw = np.round(rng.lognormal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def zipf_choice(rng, n: int, k: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=p / p.sum())


def prefill_buckets(lo: int, hi: int, max_len: int) -> List[int]:
    """The power-of-two prefill widths that suffixes of ``lo..hi`` tokens
    can ask the engine for."""
    out, p = [], 1
    while p < lo:
        p *= 2
    while True:
        out.append(min(p, max_len))
        if p >= hi or p >= max_len:
            break
        p *= 2
    return out


class Pending:
    """A request the generator made, with its due time."""

    __slots__ = ("req", "due", "session", "turn", "submitted", "first_seen",
                 "done_seen")

    def __init__(self, req, due, session=None, turn=0):
        self.req, self.due, self.session, self.turn = req, due, session, turn
        # host-clock times at which the harness submitted the request and
        # saw its first token and its terminal state (set by the harness,
        # not by the program)
        self.submitted = self.first_seen = self.done_seen = None


class Traffic:
    """The generator protocol the harness drives.

    ``poll(now, finished)`` returns the requests due by ``now``;
    ``finished`` lists ``(Pending, seen)`` pairs that reached a terminal
    state since the last poll.  ``before_window`` runs the traffic that
    comes before the measured window, ``after_window`` what follows it,
    and ``counted`` names the requests the window answers for."""

    def __init__(self, mix: dict, *, seed: int, vocab: int, max_len: int,
                 slots: int, horizon_s: float, request_cls):
        self.mix, self.vocab, self.max_len = mix, vocab, max_len
        self.slots, self.horizon_s = slots, horizon_s
        self.Request = request_cls
        self._uid = 0
        self.rng = np.random.default_rng(int(seed))
        # first tokens are drawn without replacement from this order
        self._firsts = iter(self.rng.permutation(vocab).tolist())

    def tokens(self, n: int) -> List[int]:
        if n <= 0:
            return []
        return [next(self._firsts)] + self.rng.integers(
            0, self.vocab, n - 1).tolist()

    def request(self, prompt, max_new):
        self._uid += 1
        return self.Request(uid=self._uid, prompt=list(prompt),
                            max_new_tokens=int(max_new))

    def poll(self, now: float, finished=()) -> List[Pending]:
        raise NotImplementedError

    def next_due(self) -> Optional[float]:
        """Earliest time something new falls due, if known now."""
        return None

    def prefill_bounds(self) -> Tuple[int, int]:
        """Least and largest number of prompt tokens one admission can
        have to prefill (the prefill shapes to warm up)."""
        raise NotImplementedError

    def before_window(self, drv) -> None:
        """Traffic before the window opens (``drv`` is the harness's
        ``Feeder``)."""

    def after_window(self, drv, t_open: float, t_close: float) -> None:
        """Traffic after the window closes."""

    def counted(self, drv, t_open: float, t_close: float):
        """``(Pending, due)`` of the requests the window answers for, on
        the host clock."""
        raise NotImplementedError
