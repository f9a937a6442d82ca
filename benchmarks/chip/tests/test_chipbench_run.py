"""``run.py`` refuses to measure without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import chipbench_tiny

CHECKOUT = chipbench_tiny.CHECKOUT
ARGS = ["--workload", "smollm_135m.chat_poisson", "--seed",
        str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py"] + ARGS, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to measure."""
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(CHECKOUT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
