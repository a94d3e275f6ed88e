"""95th percentile, over the requests due in the window, of the time per
output token after the first, (done - first token) / (tokens - 1), both
seen on the host at the end of an engine step; a request that was not
served to the end counts as infinite."""
import math

from bench.harness import percentile


def read(rec):
    xs = []
    for p, _ in rec.due:
        n = len(p.req.output)
        if p.req.state != "DONE":
            xs.append(math.inf)
        elif n > 1:
            xs.append((p.done_seen - p.first_seen) * 1e3 / (n - 1))
    return percentile(xs, 95) if xs else None
