"""Process start to the opening of the window: imports, weights, engine,
compilation or cache loads, warm-up and the traffic before the window."""


def read(rec):
    return rec.setup_s
