"""Without a TPU the benchmark exits non-zero and prints no result; a
directory with only the benchmark's files does the same; importing the
harness touches no device."""
import os
import shutil
import subprocess
import sys

import conftest

RUN = os.path.join("benchmarks", "chip", "run.py")
ARGS = ["--workload", "xlstm-125m.seq1k", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(out):
    return not any(line.startswith("{") for line in out.splitlines())


def test_exits_without_tpu():
    p = _run(conftest.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


def test_exits_with_benchmark_files_only(tmp_path):
    shutil.copy(os.path.join(conftest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(conftest.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_import_touches_no_device():
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import run, harness, program, trace_reduce, checks, cells, "
            "flops, calibrate\n"
            "import reference.train, reference.xlstm\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            % (conftest.BENCH, os.path.join(conftest.ROOT, "src")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
