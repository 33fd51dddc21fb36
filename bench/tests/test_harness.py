"""The harness end to end on the CPU at PN(5): the result line, and the
runs that must end without one."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT, last_json

ARGS = ["--workload", "pn31.points", "--seed", "18446744073709551557",
        "--seconds", "0.5", "--trace", "0"]


def test_tiny_run_prints_one_result_line(tiny_cell, capsys):
    run, _config, _mix = tiny_cell
    assert run.main(ARGS) == 0
    out, err = capsys.readouterr()
    res = last_json(out)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"sweep_s", "sim_steps_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    tail = err.strip().splitlines()[-3:]
    assert [ln.split()[1] for ln in tail] == list(res["checks"])


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_non_tpu_device_exits_nonzero():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_repro_perf_is_refused():
    p = _run(ROOT, {"REPRO_PERF": "sim_backend=jax"})
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0 and '"correct"' not in p.stdout
