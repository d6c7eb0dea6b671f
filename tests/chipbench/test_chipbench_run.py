"""run.py refuses to run without a TPU, and without the program beside
it, printing no result."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import registry

ARGS = ["--workload", "chatglm3-6b-bf16.chat-shared", "--seed",
        str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj)


def test_exits_nonzero_without_a_tpu():
    p = _run(str(registry.ROOT))
    assert p.returncode != 0
    assert "TPU" in p.stderr
    _no_result(p)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = registry.load_benchmark()
    shutil.copy(registry.BENCHMARK, tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(registry.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "repro" in p.stderr          # the program is not there
    _no_result(p)
