"""Mean host time of an admission in the window: each ``admit`` span's
duration less the ``prefill`` spans inside it, which leaves the queue
scan, page planning, copy-on-write dispatch, prefill operands and the
radix-tree bookkeeping.  A program without ``admit`` spans gives
nothing."""


def read(rec):
    admits = [s for s in rec.spans if s["cat"] == "admit"]
    if not admits:
        return None
    prefills = [s for s in rec.spans if s["cat"] == "prefill"]
    own = []
    for a in admits:
        inner = sum(p["t1"] - p["t0"] for p in prefills
                    if a["t0"] <= p["t0"] and p["t1"] <= a["t1"])
        own.append(a["t1"] - a["t0"] - inner)
    return sum(own) / len(own) * 1e3
