"""The Pallas paged decode-attention kernel's share of its roofline over
the traced window: the least time the chip could take for the work the
kernel was given (the larger of its FLOPs over peak bf16 and its bytes
over HBM bandwidth; live KV pages read, query read, output and new KV row
written, for the rows that decoded a token), over the kernel's device
time in the trace."""
from bench import costs
from bench.trace_reduce import matching

# the kernel's ops are named after its jitted wrapper in kernels/ops.py
KERNEL = r"^%paged_decode_attention_fused\b"


def read(rec):
    if rec.trace is None:
        return None
    t, _ = matching(rec.trace["ops"], KERNEL)
    steps = rec.steps
    rows = sum(s.decode_rows for s in steps)
    if not t or not rows:
        return None
    flops, nbytes = costs.paged_attention_cost(
        rec.model, rec.page_size, rows, sum(s.ctx_sum for s in steps),
        sum(s.pages_sum for s in steps))
    least = max(flops / rec.peaks["bf16_flops_per_s"],
                nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
