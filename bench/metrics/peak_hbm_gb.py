"""The device's peak bytes in use (``memory_stats``) after the window."""


def read(rec):
    b = rec.memory_peak_bytes
    return b / 1e9 if b else None
