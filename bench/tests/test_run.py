"""The command refuses to run where there is no TPU, and where the
checkout holds only the benchmark and not the program."""
import os
import subprocess
import sys

import pytest

import harness
import run


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bcsstk32.cold",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_require_chips_on_cpu():
    with pytest.raises(SystemExit):
        run.require_chips(1)


def test_program_only_from_checkout(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "ROOT", tmp_path)
    with pytest.raises((SystemExit, ImportError)):
        run.import_program()


def test_refuses_cell_its_driver_cannot_run():
    cell = harness.load_cell("bcsstk32.cold")
    cell.chips = 4
    with pytest.raises(SystemExit, match="closed_loop runs on"):
        harness.driver_class(cell)
    cell.traffic = dict(cell.traffic, driver="no_such_driver")
    with pytest.raises(FileNotFoundError):
        harness.driver_class(cell)
