"""The harness finds a cell's pieces by name, so a later cell is data: a
throwaway configuration, mix and metric added as files and entries run
through lookup and last-line assembly on the CPU at a tiny size. Without a
TPU the command itself refuses to report anything."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, run
from conftest import TINY_KV, TINY_ROWS, TINY_SERVE, make_checkout


def _run(root, workload, *, trace=0, seconds=0.3, seed=7, control=False):
    out, err = io.StringIO(), io.StringIO()
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    rc = run.execute(args, root=root, require_tpu=False, control=control,
                     out=out, err=err)
    return rc, out.getvalue(), err.getvalue()


def test_lookup_finds_added_files(checkout):
    bench = harness.load_benchmark(checkout)
    cell = harness.find_cell(bench, TINY_KV, checkout / "chipbench")
    assert cell.config["cache"]["pool_pages"] == 64
    assert cell.traffic["driver"] == "dma_rounds"
    assert [m["name"] for m in cell.end_to_end] == [
        "copy_GBps", "chain_p95_ms", "setup_s"]
    assert "tiny_rounds" in [m["name"] for m in cell.per_layer]
    reader = harness.load_reader("tiny_rounds", checkout / "chipbench")
    view = run.RunView(cell=TINY_KV, spans={}, counts={"rounds": 3},
                       trace=None, peaks={}, chips=1)
    assert reader(view) == 3
    with pytest.raises(harness.CellError):
        harness.find_cell(bench, "no.such_cell", checkout / "chipbench")


@pytest.mark.parametrize("workload", [TINY_KV, TINY_ROWS])
def test_tiny_run_assembles_last_line(checkout, workload):
    rc, out, err = _run(checkout, workload)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"copy_GBps", "chain_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["metrics"]["copy_GBps"]["unit"] == "GB/s"
    assert line["device"]["platform"] == "cpu"
    assert all(c == {"value": 0.0, "limit": 0.0}
               for c in line["checks"].values())
    tail = err.strip().splitlines()[-2:]
    assert [t.split(":")[0] for t in tail] == [
        f"check {name}" for name in line["checks"]]
    assert all(t.endswith(" ok") for t in tail)


def test_tiny_serve_run(checkout):
    rc, out, err = _run(checkout, TINY_SERVE, seconds=0.6)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, err[-2000:]
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert list(line["checks"]) == ["missing_requests", "served_gap_max"]


def test_no_device_metrics_without_a_chip(checkout):
    # A traced run reads the TPU planes of the profile; on the CPU there are
    # none, and the run stops rather than report a device number.
    with pytest.raises(RuntimeError, match="no TPU program"):
        _run(checkout, TINY_KV, trace=1)


def _command(root, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_tpu(checkout):
    proc = _command(checkout, TINY_KV)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    root = make_checkout(tmp_path, with_src=False)
    proc = _command(root, TINY_KV)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
