"""95th percentile, over the requests due in the window, of the time a
request waited in the engine's queue: from its submit to the admission
that took it off the queue for the prefill that served it
(``Request.dequeue_time``).  Read from the requests' own stamps, not the
window's spans, so the longest waits, which end after the window closes,
still count; a request that was never dequeued counts as infinite.  A
program whose requests carry no such stamp gives nothing."""
import math

from bench.harness import percentile


def read(rec):
    if not rec.due or not hasattr(rec.due[0][0].req, "dequeue_time"):
        return None
    xs = [(p.req.dequeue_time - p.req.submit_time) * 1e3
          if p.req.dequeue_time is not None else math.inf
          for p, _ in rec.due]
    return percentile(xs, 95)
