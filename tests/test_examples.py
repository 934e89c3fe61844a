"""Smoke-run every example script (the BASELINE configs) in a subprocess on
the CPU mesh — the scripts are user-facing entry points and must stay
runnable. On the chip the same entry points run through ``python bench.py
--smoke``, whose parent never imports jax (a pytest parent of the ``-m tpu``
tier holds the chip, so its children could not)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    ("train_resnet.py", ["--steps", "2", "--batch", "8",
                         "--image-size", "32", "--arch", "resnet18"]),
    ("finetune_bert.py", ["--steps", "2"]),
    ("train_ppyoloe.py", ["--steps", "1", "--image-size", "64"]),
    ("train_llama_hybrid.py", ["--dp", "2", "--mp", "2", "--steps", "2"]),
    ("train_deepfm.py", ["--steps", "2", "--batch", "32"]),
]


@pytest.mark.parametrize("script,args", CASES, ids=[c[0] for c in CASES])
@pytest.mark.slow
def test_example_runs(script, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert out.returncode == 0, f"{script} failed:\n{out.stdout}\n{out.stderr}"
    assert "loss" in out.stdout
