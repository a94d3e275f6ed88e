"""A copy of the benchmark with one more configuration, traffic mix and
cell, all small enough for the CPU, added the way a later change adds
them: as new files and new entries in BENCHMARK.json."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.chat"

CONFIG = {
    "name": "tiny-qwen2", "source": "https://huggingface.co/Qwen/Qwen2-7B",
    "arch": "qwen2-7b", "adapter": "dense", "reference": "dense",
    "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 512},
    "model": {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 512, "qkv_bias": True, "rope_theta": 1000000.0,
              "rms_norm_eps": 1e-06},
    "reduced": ["num_hidden_layers", "hidden_size"]}
TRAFFIC = {
    "generator": "open_sessions", "shape_seed": 1, "sessions_per_s": 8.0,
    "preroll_s": 0.3, "drain_s": 30.0,
    "system": {"count": 2, "length": 10, "zipf_s": 1.1},
    "turns": {"min": 1, "max": 2},
    "message": {"median": 5, "sigma": 0.5, "min": 3, "max": 8},
    "output": {"median": 10, "sigma": 0.5, "min": 6, "max": 16},
    "think_s": 0.1}


def make(tmp: Path, limit: float, extra_metric: str = None,
         traffic: dict = None) -> Path:
    """The benchmark copied under ``tmp`` plus the tiny cell; the cell's
    check uses ``limit``.  ``extra_metric`` names a new per-layer metric
    whose reader file is added too; ``traffic`` replaces the tiny mix."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp / "bench"
    (b / "configs" / "tiny-qwen2.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "tiny-chat.json").write_text(json.dumps(traffic or TRAFFIC))
    (b / "workloads" / f"{CELL}.json").write_text(json.dumps({
        "engine": {"slots": 2, "max_len": 64, "page_size": 8,
                   "num_pages": None, "ticks_per_sync": 4,
                   "attn_impl": "pallas_paged", "sample_impl": "pallas"},
        "check": {"sample_requests": 6, "sample_tokens": 24,
                  "max_widest_gap": limit}}))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-qwen2", "source": CONFIG["source"],
                             "file": "bench/configs/tiny-qwen2.json",
                             "reduced": CONFIG["reduced"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-qwen2",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "phi3.chat-shared" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    if extra_metric:
        (b / "metrics" / f"{extra_metric}.py").write_text(
            "def read(rec):\n"
            "    return float(sum(s.decode_rows for s in rec.steps))\n")
        bench["per_layer"].append({
            "name": extra_metric, "unit": "tokens", "better": "higher",
            "source": "host_clock", "layer": "serve.engine decode window",
            "moves": "output_tokens_per_s", "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def chat_limit() -> float:
    cell = json.loads(
        (ROOT / "bench" / "workloads" / "phi3.chat-shared.json").read_text())
    return float(cell["check"]["max_widest_gap"])
