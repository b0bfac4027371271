"""``chip_smoke.py`` on the CPU: its body at smoke widths, and its refusal.

The script's ``main()`` serves granite-3-2b at published widths and only
runs on a TPU. Its body takes the config, so here it runs under the same
assertions at ``configs.smoke("granite_3_2b")``: every request completes
with its token budget in vocab, prefix pages are reused, nothing replays,
the step holds the Pallas kernel exactly when on a TPU (so not here), and
the kernel (interpreted) agrees with ``ref`` on one step, and the same
comparison fails a kernel with a planted fault. The four-chip body runs
on four virtual CPU devices.
"""

import os
import pathlib
import subprocess
import sys

import pytest

from repro import configs
from repro.kernels.paged_attention import ops as pa_ops

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_one_chip_body_at_smoke_width():
    lines = []
    out = chip_smoke.run_one_chip(configs.smoke("granite_3_2b"), seed=0,
                                  log=lines.append)
    assert set(out["tokens"]) == {f"shared{i}" for i in range(4)} | {
        f"solo{i}" for i in range(4)}
    assert all(len(t) == chip_smoke.NEW_TOKENS
               for t in out["tokens"].values())
    assert out["stats"]["completed"] == 8
    assert out["kernel_vs_ref"]["rel_err"] <= chip_smoke.LOGIT_RTOL
    assert any("tpu_custom_call: False" in line for line in lines), lines


@pytest.fixture(scope="module")
def served():
    """Smoke granite's weights and an engine whose pool holds the pages
    of the eight requests, as ``kernel_vs_ref`` sees them after serving."""
    cfg = configs.smoke("granite_3_2b")
    params = chip_smoke.init_params(cfg, 0)
    engine = chip_smoke.build_engine(cfg, params)
    chip_smoke.serve(engine, engine.step,
                     chip_smoke.make_requests(cfg.vocab, 0))
    return cfg, params, engine


_REAL_KERNEL = pa_ops.paged_attention_kernel
_PLANTED = {
    # attends over one position fewer: the token appended this step is lost
    "drop_appended_token": lambda q, k, v, t, n, **kw: _REAL_KERNEL(
        q, k, v, t, n - 1, **kw),
    # table entry 0 read as entry 1: one page of each slot is wrong
    "wrong_first_page": lambda q, k, v, t, n, **kw: _REAL_KERNEL(
        q, k, v, t.at[:, 0].set(t[:, 1]), n, **kw),
    # the last query head's output lost
    "zero_one_head": lambda q, k, v, t, n, **kw: _REAL_KERNEL(
        q, k, v, t, n, **kw).at[:, -1].set(0),
}


@pytest.mark.parametrize("fault", sorted(_PLANTED))
def test_kernel_vs_ref_fails_a_planted_kernel_fault(served, monkeypatch,
                                                    fault):
    """The comparison can see a wrong kernel: each planted fault breaks
    ``LOGIT_RTOL`` over the ``COMPARE_LAYERS`` the chip compares."""
    cfg, params, engine = served
    assert chip_smoke.kernel_vs_ref(cfg, params, engine, 0)["rel_err"] \
        <= chip_smoke.LOGIT_RTOL
    monkeypatch.setattr(pa_ops, "paged_attention_kernel", _PLANTED[fault])
    with pytest.raises(AssertionError):
        chip_smoke.kernel_vs_ref(cfg, params, engine, 0)


def test_main_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0, run
    assert '"ok"' not in run.stdout, run.stdout
    assert "no TPU" in run.stderr, run.stderr


def test_four_chip_body_on_virtual_devices(subproc):
    """tp=4 and four replicas on forced host devices reproduce the
    one-chip tokens with the arena split and each replica on its own
    device; the logits fallback comparison holds for both meshes."""
    code = f"""
import dataclasses, sys
sys.path.insert(0, {str(REPO)!r})
import jax
assert len(jax.devices()) == 4, jax.devices()
import chip_smoke
from repro import configs
from repro.launch.mesh import replica_meshes, serve_tp_mesh

# smoke granite has 2 KV heads; tp=4 needs them divisible by 4
cfg = dataclasses.replace(configs.smoke("granite_3_2b"), n_kv_heads=4)
out = chip_smoke.run_four_chips(cfg, seed=0)
assert out["tp"]["tokens"] == out["base"]["tokens"]
params = chip_smoke.init_params(cfg, 0)
for mesh in (serve_tp_mesh(4), replica_meshes(4, 1)[2]):
    err = chip_smoke.sharded_vs_single(cfg, params, mesh, seed=1)
    assert err["rel_err"] <= chip_smoke.LOGIT_RTOL, err
print("FOUR_OK")
"""
    assert "FOUR_OK" in subproc(code, devices=4, timeout=600)


def test_config_is_granite_at_published_widths():
    cfg = configs.get(chip_smoke.ARCH)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (40, 2048, 32, 8, 8192, 49155)
