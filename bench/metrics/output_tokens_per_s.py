"""Every output token that reached the host in the window, over the
window's length."""


def read(rec):
    return sum(s.tokens for s in rec.steps) / rec.window_s
