"""``chip_smoke.py`` rehearsed on the CPU: its phases at reduced widths
with the kernels in interpret mode, and its refusal to run without a TPU
or outside a checkout."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

import repro.configs as C
from repro.configs.reduced import reduced

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "bare directory"])
def test_chip_smoke_fails_without_tpu_or_checkout(where, tmp_path):
    script = SCRIPT
    if where == "bare directory":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=120, env=env,
                          cwd=os.path.dirname(script))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_kernel_phase_reduced(smoke):
    smoke.kernel_phase(reduced(C.get("qwen3-1.7b")), interpret=True)


@pytest.mark.parametrize("hashed", [False, True], ids=["dense", "hashed"])
def test_chip_smoke_engine_phase_reduced(smoke, hashed):
    cfg = C.get("qwen3-1.7b")
    if hashed:
        cfg = cfg.hashed_variant(0.125)
    smoke.engine_phase(reduced(cfg), max_len=64)
