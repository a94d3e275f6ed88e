"""Share of the running slots' time in the window spent waiting on other
requests' admissions: the engine's ``admit`` spans, each weighted by the
slots already decoding when it began (its ``decoding`` arg), over those
plus the ``decode_window`` spans weighted by the slots they ran
(``active``).  A program without ``admit`` spans gives nothing."""


def read(rec):
    admits = [s for s in rec.spans if s["cat"] == "admit"]
    if not admits:
        return None
    stalled = sum((s["t1"] - s["t0"]) * s["args"]["decoding"]
                  for s in admits)
    running = sum((s["t1"] - s["t0"]) * s["args"]["active"]
                  for s in rec.spans if s["cat"] == "decode")
    if stalled + running <= 0:
        return None
    return 100.0 * stalled / (stalled + running)
