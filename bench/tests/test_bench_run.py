"""`bench/run.py` as a command: no accelerator, or no program, means a
non-zero exit and no result on standard output."""
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "gcn-paper.full.c1", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _cmd(root, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=root,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_no_tpu_means_no_result(tmp_path):
    out = _cmd(ROOT, tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    bare = tmp_path / "bare"
    shutil.copytree(BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    out = _cmd(str(bare), tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
