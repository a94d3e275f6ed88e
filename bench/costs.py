"""Operations and bytes of the work the program was asked to do, computed
from shapes and from the benchmark's own count of the tokens served.
These, not the compiler's cost analysis, price every roofline and MFU
share, so no later change to the program can move them."""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDeviceError(KeyError):
    """A device kind that has no published peaks in ``peaks.json``."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(table)}); add them to bench/peaks.json "
            "with their source")
    return table[device_kind]


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer and the head
    (the embedding is a gather)."""
    D, H, K, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"], m["d_ff"])
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return m["num_layers"] * per_layer + D * m["vocab_size"]


def decode_flops(m: dict, tokens: int, ctx_sum: int) -> float:
    """Model FLOPs of ``tokens`` decoded tokens whose contexts (keys each
    attends, itself included) sum to ``ctx_sum``: two per weight, and
    four per head dimension and key for scores and values."""
    attn = 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * ctx_sum
    return 2.0 * matmul_params(m) * tokens + attn


def paged_attention_cost(m: dict, page_size: int, calls: int, ctx_sum: int,
                         pages_sum: int, itemsize: int = 2):
    """(FLOPs, bytes) of ``calls`` per-row paged decode attentions, summed
    over layers: every live KV page read (K and V), the query read, the
    output written and the new K/V row written."""
    L, H, K, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                   m["head_dim"])
    flops = 4.0 * L * H * hd * ctx_sum
    page_bytes = page_size * K * hd * itemsize
    per_call = 2 * H * hd * itemsize + 2 * K * hd * itemsize
    nbytes = L * (2 * page_bytes * pages_sum + per_call * calls)
    return flops, float(nbytes)


def pages(ctx: int, page_size: int) -> int:
    return int(math.ceil(ctx / page_size))
