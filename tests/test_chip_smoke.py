"""chip_smoke.py rehearsed on the CPU, and its refusal of the CPU.

The smoke refuses any platform but the TPU. The rehearsal gets past that
check by replacing ``chip_smoke.require_tpu`` here, in the test: the script
itself has no such option.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from pixie_tpu.utils import flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: jax.devices())
    # Small fact tables sit below the join lane's row floor.
    was = flags.device_join_min_rows
    flags.set("device_join_min_rows", 0)
    cache_dir = jax.config.jax_compilation_cache_dir
    yield
    flags.set("device_join_min_rows", was)
    # The smoke points JAX's persistent cache; later tests in this
    # worker get the process's own setting back.
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "argv",
    [
        ["--rows-log2", "16", "--join-rows", "65536"],
        ["--chips", "4", "--rows-log2", "14"],
    ],
    ids=["one_chip", "four_chip_phase"],
)
def test_rehearsal(on_cpu, capsys, argv):
    assert chip_smoke.main(argv) == 0
    out = last_line(capsys)
    assert out["ok"] is True
    assert out["device"]["platform"] == "cpu"


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *args],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_refuses_without_tpu(tmp_path, alone):
    """No TPU, or no repo around the script: non-zero exit, no result."""
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        proc = _run(["chip_smoke.py"], cwd=tmp_path)
    else:
        proc = _run([os.path.join(REPO, "chip_smoke.py")], cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
