"""Seconds of JAX compile events (trace, lower, compile or cache load)
during set-up."""


def read(rec):
    return rec.compile_setup_s
