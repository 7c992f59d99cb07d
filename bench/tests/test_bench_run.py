"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: no TPU, or no engine beside the benchmark."""

import os
import shutil
import subprocess
import sys

from benchtest import BENCH, ROOT, SF1


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SF1, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    res = _run(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".store", ".jax_cache",
                                                  ".trace", "__pycache__"))
    res = _run(str(tmp_path))
    assert res.returncode != 0
    assert res.stdout.strip() == ""
