"""chip_smoke.py: no CPU fallback, and its one-chip phases at a tiny size.

The script itself refuses to run without a TPU; its phase function is
driven here directly on the CPU, so every check it makes on the chip is
exercised (and must pass) on every test run.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_refuses_without_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert not _printed_result(out.stdout), out.stdout
    assert "no TPU" in out.stderr


def test_fails_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run(alone, tmp_path)
    assert out.returncode != 0
    assert not _printed_result(out.stdout), out.stdout


def test_one_chip_phases_pass_at_tiny_size(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.one_chip(1000, seed=0)
    lines = dict(line.split("=", 1)
                 for line in capsys.readouterr().out.splitlines())
    assert float(lines["recall_at_10"]) >= smoke.RECALL_FLOOR
    assert lines["inserts_found_top1"] == str(smoke.WAVE_INSERT)
    assert int(lines["dead_refs_before_repair"]) > 0
    assert lines["dead_refs_after_repair"] == "0"
