"""The entry point refuses to measure anything but the chip."""
import os
import shutil
import subprocess
import sys

import helpers

ARGS = ["--workload", "gan-train-custom-1chip", "--seed", "2147483649",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(helpers.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(helpers.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(helpers.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no program" in p.stderr
