"""Whole runs of each cell at a small size on the CPU: the result line's
shape, the numbers compared, and what a run refuses to do."""

import json
import os
import subprocess
import sys

import pytest

from small import SMALL, run_small

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_result_line(workload):
    result, checks, _out = run_small(workload)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == want
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert {c[0] for c in checks} == set(result["checks"])
    json.dumps(result)


def _run_cli(env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tree_lstm.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run_cli({"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_non_pallas_backend_refused():
    p = _run_cli({"JAX_PLATFORMS": "cpu", "REPRO_KERNEL_IMPL": "chunked"})
    assert p.returncode == 2 and p.stdout.strip() == ""
