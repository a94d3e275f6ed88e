"""Model FLOPs of the tokens the decode windows computed (every weight
and attention over each token's live context; idle slots and padding
excluded), over the summed decode-window time and the chip's bf16
peak."""
from bench import costs


def read(rec):
    t = sum(s["t1"] - s["t0"] for s in rec.spans if s["cat"] == "decode")
    rows = sum(s.decode_rows for s in rec.steps)
    if not t or not rows:
        return None
    ctx = sum(s.ctx_sum for s in rec.steps)
    flops = costs.decode_flops(rec.model, rows, ctx)
    return 100.0 * flops / t / rec.peaks["bf16_flops_per_s"]
