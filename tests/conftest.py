import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src"


def run_subprocess(code: str, devices: int = 1, timeout: int = 420):
    """Run python code in a fresh process with N host devices (for
    multi-device tests — the main test process keeps 1 device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:\n{out.stdout}\n"
                             f"STDERR:\n{out.stderr}")
    return out.stdout


@pytest.fixture
def subproc():
    return run_subprocess


@pytest.fixture(scope="session", autouse=True)
def shared_jit_cache():
    """One jit/compile cache shared by every engine test in the session.

    The engine-test harness (``engine_sim.CANONICAL``) pads every engine to
    one canonical device shape (4 lanes, 48 cache positions), so the dozens
    of engines built across ``test_engine.py`` / ``test_pages.py`` /
    ``test_ft.py`` with different ``slots``/``max_len`` all hit the *same*
    jitted-and-compiled step function (the module-level caches in
    ``serve/engine.py`` / ``serve/paged.py``) instead of compiling one XLA
    program per shape. Extra lanes ride the batch idle and extra cache
    positions are masked; outputs are unchanged — the bit-identity tests
    hold the padded engines to that.

    jax's on-disk compilation cache is off unless the standard
    ``JAX_COMPILATION_CACHE_DIR`` variable is set (jax reads it itself);
    the suite sets no directory of its own.
    """
    sys.path.insert(0, str(REPO / "tests"))
    import engine_sim

    engine_sim.CANONICAL.update(lane_batch=4, device_len=48)
    yield
