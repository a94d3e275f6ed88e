"""Mean decode tick: the engine's ``decode_window`` spans (launch to
tokens on the host) over the ticks each window runs."""


def read(rec):
    xs = [s["t1"] - s["t0"] for s in rec.spans if s["cat"] == "decode"]
    if not xs:
        return None
    return sum(xs) / len(xs) / rec.ticks_per_sync * 1e3
