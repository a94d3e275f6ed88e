"""Mean time of the engine's admission prefill (its ``prefill`` spans,
which end when the first tokens are on the host) in the window."""


def read(rec):
    xs = [s["t1"] - s["t0"] for s in rec.spans if s["cat"] == "prefill"]
    return sum(xs) / len(xs) * 1e3 if xs else None
