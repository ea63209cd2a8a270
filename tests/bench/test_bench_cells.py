"""Every cell of the benchmark runs end to end at smoke size on the CPU
(the SMOKE configurations, kernels in interpret mode) and returns a result
line with the contract's keys; off the chip the command itself refuses."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(cell, trace=False, seed=2**31 + 17, seconds=8.0):
    from bench.harness import run_cell

    lines = []
    res = run_cell(cell, seed, seconds, trace, allow_cpu=True,
                   log=lines.append)
    return res, lines


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_at_smoke_size(smoke_cell, name):
    res, lines = _run(smoke_cell(name))
    line = json.loads(json.dumps(res))           # what run.py prints
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert any(s.startswith("window:") for s in lines)
    assert any(s.startswith("batch occupancy:") for s in lines)


def test_traced_run_reports_program_metrics(smoke_cell):
    res, _ = _run(smoke_cell(CELLS[0]), trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in ("decode_step_ms", "window_compiles", "pages_freed_per_s",
                 "queue_wait_p90_s"):
        assert name in got, got
    # no device trace off the chip: the device readers find nothing
    assert "mfu" not in got and "busy_s" not in res["device"]


def test_run_cell_refuses_the_cpu_without_opt_in(smoke_cell):
    from bench.harness import NoChip, run_cell

    with pytest.raises(NoChip):
        run_cell(smoke_cell(CELLS[0]), 1, 1.0, False)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "5", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _json_lines(out):
    found = []
    for ln in out.splitlines():
        try:
            found.append(json.loads(ln))
        except ValueError:
            pass
    return found


def test_command_fails_without_a_tpu():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert not _json_lines(r.stdout)


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no program to run."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0
    assert not _json_lines(r.stdout)
