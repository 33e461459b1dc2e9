"""``bench/run.py`` prints no result and exits non-zero on a host without
a TPU, and in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench.harness import spec

ARGS = ["--workload", "qwen2.5-3b.chat", "--seed", "2147483650",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_means_no_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_alone_means_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
