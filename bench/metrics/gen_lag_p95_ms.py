"""95th percentile, over the requests due in the window, of how late the
harness submitted each one after its due time: the part of the time to
first token that the engine's host loop, not the traffic, decides."""
from bench.harness import percentile


def read(rec):
    xs = [(p.submitted - due) * 1e3 for p, due in rec.due
          if p.submitted is not None]
    return percentile(xs, 95) if xs else None
