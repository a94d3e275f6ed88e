"""``benchmarks.common.timed`` must block on async JAX outputs before it
reads the clock (DESIGN.md §14): a sleepy dummy computation must not
time as ~0, and host values must stay cheap."""
import time

from benchmarks.common import timed


class _AsyncResult:
    """Mimics a dispatched-but-unfinished jax.Array: the work only
    happens when someone blocks on it."""

    def __init__(self, seconds):
        self._seconds = seconds

    def block_until_ready(self):
        time.sleep(self._seconds)
        return self


def test_timed_blocks_on_async_outputs():
    delay = 0.05
    out, us = timed("sleepy", lambda: _AsyncResult(delay), repeats=2)
    assert isinstance(out, _AsyncResult)
    # the seed timed() returned in microseconds here; the blocking clock
    # must charge (at least) the dispatched work to every repeat
    assert us >= 0.8 * delay * 1e6, f"async work not timed: {us:.1f}us"


def test_timed_still_cheap_for_host_values():
    out, us = timed("host", lambda: (1.0, None, {"a": 2}), repeats=2)
    assert out == (1.0, None, {"a": 2})
    assert us < 1e5
