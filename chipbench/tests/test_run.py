import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "serve-qwen4b-decode", "--seed", str(2**31 + 1), "--seconds", "1",
         "--trace", "0"], cwd=cwd, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
