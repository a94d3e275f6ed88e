"""95th percentile, over the requests due in the window, of the time from
a request's due time to its first token on the host (seen at the end of
an engine step); a request that was not served to the end counts as
infinite."""
import math

from bench.harness import percentile


def read(rec):
    xs = [(p.first_seen - due) * 1e3
          if p.req.state == "DONE" and p.first_seen is not None else math.inf
          for p, due in rec.due]
    return percentile(xs, 95) if xs else None
