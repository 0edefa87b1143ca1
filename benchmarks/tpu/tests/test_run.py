"""``run.py`` refuses, with no result line, where it cannot measure."""

import os
import shutil
import subprocess
import sys

from tpubench import spec

RUN = ["--workload", "phi4-mini.chat", "--seed", str(2**31 + 11), "--seconds", "1"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "benchmarks/tpu/run.py"), *RUN],
                          capture_output=True, text=True, env=env, timeout=300)


def test_no_accelerator_means_no_result():
    out = _run(spec.ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_only_the_benchmark_files_means_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert "{" not in out.stdout and "no program" in out.stderr
