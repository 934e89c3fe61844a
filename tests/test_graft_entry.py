"""The multichip dry-run pins its own virtual CPU mesh, or says why not.

``dryrun_multichip`` sets the platform and device count through
``jax.config`` when nothing has queried a device yet; a process whose
backend is already up as something else gets a clear error instead of a
backend torn down through jax's internals. These tests run the entry
module in a fresh subprocess without the conftest's CPU pins.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_overrides=None, timeout=600):
    env = dict(os.environ)
    # drop the CPU pins the test conftest added: the entry point must pin
    # for itself
    if env.get("JAX_PLATFORMS") == "cpu":
        del env["JAX_PLATFORMS"]
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f)
    if env_overrides:
        env.update(env_overrides)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_dryrun_multichip_survives_unscrubbed_env():
    r = _run("import __graft_entry__ as g; g.dryrun_multichip(8)")
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "dryrun_multichip OK" in r.stdout


@pytest.mark.slow
def test_dryrun_multichip_after_jax_import():
    # driver may import jax (and even list devices) before calling us
    r = _run(
        "import jax\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n")
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "dryrun_multichip OK" in r.stdout


def test_dryrun_multichip_after_backend_init_says_why():
    # the backend is already up with one device: no switch, a clear error
    r = _run(
        "import jax\n"
        "jax.devices()\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n", env_overrides={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a 8-device CPU backend" in r.stderr, r.stderr
