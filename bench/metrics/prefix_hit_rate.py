"""Prompt tokens served from the prefix cache over prompt tokens
admitted, counted by the paged engine over the window."""


def read(rec):
    d = {k: rec.stats_close[k] - rec.stats_open[k]
         for k in ("prefix_tokens", "prompt_tokens")}
    return d["prefix_tokens"] / d["prompt_tokens"] if d["prompt_tokens"] \
        else None
