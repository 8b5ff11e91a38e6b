import os
import shutil
import subprocess
import sys

from harness.files import BENCH, ROOT


def run_cli(cwd, script, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script, *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


ARGS = ("--workload", "g500s15.pr-direct", "--seed", "3", "--seconds", "1", "--trace", "0")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = run_cli(ROOT, os.path.join("bench", "run.py"), *ARGS)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_control_without_a_tpu_exits_nonzero():
    out = run_cli(ROOT, os.path.join("bench", "control.py"), "--workload", "g500s15.pr-direct", "--seeds", "1")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    out = run_cli(tmp_path, os.path.join("bench", "run.py"), *ARGS)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_unknown_workload_exits_nonzero():
    out = run_cli(ROOT, os.path.join("bench", "run.py"), "--workload", "nope", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_result_line_is_strict_json():
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.finite({"checks": {"rel_err_l2": {"value": float("inf")}}, "x": [1.5, float("nan")]})
    assert json.loads(json.dumps(line, allow_nan=False)) == {
        "checks": {"rel_err_l2": {"value": None}}, "x": [1.5, None]}
