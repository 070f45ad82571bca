"""``bench/run.py`` prints no result and exits nonzero without a TPU,
and in a checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

from lib import harness


def run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.cifar10.paper-sparse", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero():
    p = run_cli(harness.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert no_result(p.stdout)


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(str(tmp_path))
    assert p.returncode != 0
    assert no_result(p.stdout)
