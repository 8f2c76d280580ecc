"""The command itself: no chip means no result, and neither does a
directory holding only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "yi-9b.tenants.poisson", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_no_accelerator_exits_nonzero_without_a_result():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": "",
                    "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "No module named 'repro'" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
