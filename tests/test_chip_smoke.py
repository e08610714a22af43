"""chip_smoke.py: refuses to run without a TPU, and its serve and train
phases hold at the reduced configs on the CPU (the chip runs them at full
width)."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

from repro.configs import get_config

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_serve_phase_smoke_config():
    summary = _load().serve_phase(get_config("qwen3_4b", smoke=True),
                                  requests=4, prompt_len=48, block=16,
                                  max_new=8, batch=4)
    assert "cached == uncached streams" in summary


def test_train_phase_smoke_config():
    summary = _load().train_phase(get_config("mamba2_780m", smoke=True),
                                  (("adamw", 4, 64),), steps=3)
    assert "trained 3 steps + 1 after load_step" in summary
