"""Fixtures for the benchmark's own tests: a throwaway checkout holding a
tiny configuration, traffic mix and per-layer metric, added as files and
entries the way a later cell is added."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_KV = "tiny.kv_runs"
TINY_ROWS = "tiny.row_scatter"
TINY_SERVE = "tiny-qwen.chat"

_TINY_METRIC = '''"""Rounds the window ran (a throwaway per-layer metric)."""


def read(run):
    return run.counts.get("rounds")
'''


def make_checkout(dest: pathlib.Path, *, with_src: bool = True
                  ) -> pathlib.Path:
    """Copy BENCHMARK.json and chipbench/ to ``dest`` and add two tiny
    cells, their configuration, mixes and a metric, as files only."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    if with_src:
        (dest / "src").symlink_to(ROOT / "src")
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    cb = dest / "chipbench"
    cfg = {"source": "test", "reduced": [],
           "cache": {"kind": "kv_pages", "pools": 3, "page_tokens": 4,
                     "row_elems": 8, "pool_pages": 64, "pool_rows": 2048,
                     "dtype": "bfloat16"}}
    (cb / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "tiny_runs.json").write_text(json.dumps({
        "driver": "dma_rounds", "pattern": "page_runs",
        "requests_per_round": 2, "context_median": 16,
        "context_sigma": 1.0, "context_clip": [4, 32],
        "run_pages": [2, 4], "gap_pages": [1, 2], "ring_capacity": 256,
        "warmup_rounds": 1}))
    (cb / "traffic" / "tiny_rows.json").write_text(json.dumps({
        "driver": "dma_rounds", "pattern": "row_scatter", "batch": 8,
        "staging_steps": 2, "context_median": 64, "context_sigma": 1.0,
        "context_clip": [16, 256], "headroom_tokens": 16,
        "ring_capacity": 64, "warmup_rounds": 1}))
    (cb / "metrics" / "tiny_rounds.py").write_text(_TINY_METRIC)
    # The registry's reduced qwen2.5-3b: 2 layers, d_model 64, 4 heads.
    qwen = json.loads((cb / "configs" / "qwen2.5-3b.json").read_text())
    qwen.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, num_hidden_layers=2, vocab_size=512)
    qwen.update(max_position_embeddings=32)
    qwen["serve"].update(registry_reduced=True, capacity=4)
    (cb / "configs" / "tiny-qwen.json").write_text(json.dumps(qwen))
    chat = json.loads((cb / "traffic" / "serve_chat.json").read_text())
    chat.update(rate_per_s=12, prompt_median=8, prompt_clip=[4, 16],
                output_median=8, output_clip=[4, 12], check_requests=8,
                check_width=32, check_block=4, served_gap_limit=0.03)
    (cb / "traffic" / "tiny_chat.json").write_text(json.dumps(chat))
    bench["configs"] += [
        {"name": "tiny", "source": "test",
         "file": "chipbench/configs/tiny.json", "reduced": [], "why": "test"},
        {"name": "tiny-qwen", "source": "test",
         "file": "chipbench/configs/tiny-qwen.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] += [
        {"name": TINY_KV, "config": "tiny", "traffic": "tiny_runs",
         "chips": 1, "why": "test"},
        {"name": TINY_ROWS, "config": "tiny", "traffic": "tiny_rows",
         "chips": 1, "why": "test"},
        {"name": TINY_SERVE, "config": "tiny-qwen", "traffic": "tiny_chat",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if m["name"] in ("copy_GBps", "chain_p95_ms"):
            m["workloads"] += [TINY_KV, TINY_ROWS]
        if m["name"] in ("ttft_p90_ms", "itl_p95_ms"):
            m["workloads"] += [TINY_SERVE]
    bench["per_layer"].append(
        {"name": "tiny_rounds", "unit": "rounds", "better": "higher",
         "source": "host_clock", "layer": "test", "moves": "copy_GBps",
         "workloads": [TINY_KV, TINY_ROWS]})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(tmp_path)
