"""`benchmarks/run.py` measures on a TPU or not at all: on this CPU it
exits non-zero and prints no result line, and so it does in a
directory that holds only BENCHMARK.json and the benchmark's paths."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "solo1-500tx.catchup", "--seed", "7", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "TPU" in p.stderr and "nothing measured" in p.stderr


def test_alone_with_the_benchmark_files_it_refuses(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run(root)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


def test_an_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "nope", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and not _result_lines(p.stdout)
