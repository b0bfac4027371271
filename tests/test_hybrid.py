"""The Mamba-2 / attention hybrid (granite-4.0-h) on the paged backend.

The float32 reference is the benchmark's own (``bench/models/
granite_hybrid.py``), which imports nothing of the program; the weights
are the benchmark's seeded draw at its model file's ``small`` size (two
periods of a Mamba-2 and an attention layer, the published state of 128
and heads of 64). Engine behaviour is checked on the registry's smoke
config.

Tolerances, with their reasons:

* logits of the paged step against the reference: 3% of the largest
  reference logit. The program computes in bf16 (weights, activations,
  conv tails, KV) around a float32 state; at this size the widest
  difference read 1.6–1.8% over seeds 0–2, and all of it is that
  rounding: the same step with weights, activations and both arenas in
  float32 reads 6e-7 (checked below to 1e-5). Dropping D·x, the conv
  bias or the gate moves it to 44–137%.
* a state rounded to bf16 at every step hides under the bf16
  activations: through the bf16 step it reads 1.6–1.7% over seeds 0–2,
  against 1.6–1.8% clean. So that planted fault is compared through the
  float32 step, where it reads 1.7e-3–3.0e-3 against 6e-7 clean and the
  1e-5 limit.
* the recurrence against float64: 1e-5 relative over 64 steps of a long
  memory, where float32 reads 8e-8 and a bf16 state 3e-3.
"""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_sim import (Simulator, burst_trace, make_engine, make_requests,
                        smoke_params, tokens_of)
from repro import configs
from repro.kernels.ssm_decode import ref as ssm_ref
from repro.kernels.ssm_decode.kernel import ssm_decode_kernel
from repro.models import hybrid, mamba2, registry, transformer
from repro.models.config import ModelConfig
from repro.serve import paged, tracing
from repro.serve.engine import ContinuousBatchingEngine, Request
from repro.serve.pages import PageTable
from repro.sharding import rules as R

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec, weights  # noqa: E402

LOGIT_TOL = 0.03
F32_TOL = 1e-5
RECUR_TOL = 1e-5
ARCH = "granite_4_0_h_micro"


def _bench():
    cfg = spec.load_json(ROOT / "bench/configs/granite-4.0-h-micro.json")
    model = spec.model(cfg)
    small = model.small(cfg)
    return model, small, ModelConfig(**model.program_config(small))


def _paged_logits(cfg, params, tokens, dtype=None):
    """(S, V) float32 logits of one sequence fed token by token through the
    paged step the engine launches, beside an idle lane; ``dtype`` puts
    the KV arena and the conv tails in another dtype than the engine's."""
    ps = 16
    pages = -(-len(tokens) // ps)
    if dtype is None:
        k, v = registry.paged_pool_init(cfg, 2 * pages + 1, ps)
        state = registry.state_init(cfg, 2)
    else:
        k, v = transformer.paged_pool_init(cfg, 2 * pages + 1, ps, dtype,
                                           layers=hybrid.n_attention(cfg))
        state = mamba2.SSMCache.init(cfg, 2, dtype=dtype,
                                     layers=hybrid.n_mamba(cfg))
    call = jax.jit(paged._decode_call(cfg, None, None, "model"))
    tables = jnp.asarray([list(range(pages)), [2 * pages] * pages], jnp.int32)
    mask = jnp.asarray([True, False])
    out = []
    for t, tok in enumerate(tokens):
        logits, k, v, state = call(params, k, v, state, tables,
                                   jnp.asarray([t, 0], jnp.int32),
                                   jnp.asarray([tok, 0], jnp.int32), mask)
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)


def _reference_error(seed=0, n=24, f32=False):
    model, small, cfg = _bench()
    params = weights.make(small, seed)
    tokens = spec.rng(seed, "tokens").integers(0, cfg.vocab, n)
    want = np.asarray(jax.jit(lambda p, t: model.logits(small, p, t))(
        params, jnp.asarray(tokens, jnp.int32)))
    if f32:
        got = _paged_logits(
            dataclasses.replace(cfg, dtype="float32"),
            jax.tree.map(lambda a: a.astype(jnp.float32), params), tokens,
            jnp.float32)
    else:
        got = _paged_logits(cfg, params, tokens)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_step_matches_the_float32_reference(seed):
    """Prompt then decode, 24 tokens through one lane of the paged step
    (KV arena over the attention layers, state arena beside it), against
    the reference's full forward pass."""
    assert _reference_error(seed) < LOGIT_TOL


def test_paged_step_in_float32_is_the_reference():
    """The same step with weights, activations, KV arena and conv tails in
    float32: the program's equations are the reference's, to rounding."""
    assert _reference_error(f32=True) < F32_TOL


def _no_d(monkeypatch):
    step = ssm_ref.ssm_step
    monkeypatch.setattr(ssm_ref, "ssm_step",
                        lambda s, x, dt, A, B, C, D: step(s, x, dt, A, B, C,
                                                          D * 0))


def _no_conv_bias(monkeypatch):
    conv = mamba2._conv_step

    def no_bias(v, lp, group, tail):
        lp = dict(lp, **{f"conv_{group}_b": lp[f"conv_{group}_b"] * 0})
        return conv(v, lp, group, tail)

    monkeypatch.setattr(mamba2, "_conv_step", no_bias)


def _no_gate(monkeypatch):
    gate = mamba2._gate_out
    # silu(30) is 30 to the last bit: y·silu(z) becomes a plain scaling,
    # which the RMSNorm divides out
    monkeypatch.setattr(mamba2, "_gate_out",
                        lambda y, z, lp, cfg, dtype: gate(
                            y, jnp.full_like(z, 30.0), lp, cfg, dtype))


def _bf16_state(monkeypatch):
    step = ssm_ref.ssm_step

    def bf16_state(state, *args):
        y, s = step(state, *args)
        return y, s.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(ssm_ref, "ssm_step", bf16_state)


@pytest.mark.parametrize("fault,f32", [(_no_d, False), (_no_conv_bias, False),
                                       (_no_gate, False), (_bf16_state, True)],
                         ids=["no_D", "no_conv_bias", "no_gate", "bf16_state"])
def test_planted_faults_fail_the_reference_comparison(monkeypatch, fault, f32):
    """Each fault fails the paged step's comparison with the reference; a
    bf16 state, which the bf16 step's own rounding hides, through the
    float32 step (module docstring)."""
    fault(monkeypatch)
    assert _reference_error(f32=f32) > (F32_TOL if f32 else LOGIT_TOL)


def _f64_recurrence(state, xs, dts, A, Bs, Cs, D):
    h = np.asarray(state, np.float64)
    ys = []
    for x, dt, B, C in zip(xs, dts, Bs, Cs):
        x, dt, B, C = (np.asarray(a, np.float64) for a in (x, dt, B, C))
        h = (h * np.exp(dt * np.asarray(A, np.float64))[..., None, None]
             + (dt[..., None] * x)[..., None] * B[:, None, None, :])
        ys.append((h * C[:, None, None, :]).sum(-1)
                  + np.asarray(D, np.float64)[:, None] * x)
    return np.stack(ys), h


def _recurrence_error(steps=64):
    b, h, p, n = 2, 4, 16, 32
    k = jax.random.split(jax.random.key(7), 5)
    xs = jax.random.normal(k[0], (steps, b, h, p)).astype(jnp.bfloat16)
    # a long memory: dt·A near 0.02, so a state sums ~50 steps
    dts = 0.01 * jnp.ones((steps, b, h)) + 0.01 * jax.random.uniform(
        k[1], (steps, b, h))
    A = -jnp.linspace(1.0, 2.0, h)
    Bs = jax.random.normal(k[2], (steps, b, n)).astype(jnp.bfloat16)
    Cs = jax.random.normal(k[3], (steps, b, n)).astype(jnp.bfloat16)
    D = jnp.linspace(0.5, 1.5, h)
    arena = jnp.zeros((2, b, h, p, n), jnp.float32)
    ys = []
    for t in range(steps):
        y, arena = ssm_ref.ssm_decode(arena, 1, xs[t], dts[t], A, Bs[t],
                                      Cs[t], D)
        ys.append(y)
    want, _ = _f64_recurrence(np.zeros((b, h, p, n)), xs, dts, A, Bs, Cs, D)
    return float(np.abs(np.stack(ys) - want).max() / np.abs(want).max())


def test_the_recurrence_keeps_float32(monkeypatch):
    """64 steps of the decode recurrence against float64: f32 passes, a
    state rounded to bf16 each step does not."""
    assert _recurrence_error() < RECUR_TOL
    _bf16_state(monkeypatch)
    assert _recurrence_error() > 10 * RECUR_TOL


def test_ssm_decode_kernel_equals_ref_and_keeps_masked_lanes():
    """The Pallas kernel in interpret mode against ``ref.py`` on one layer
    of an arena: y and the live rows to float32 rounding; a masked lane's
    rows and every other layer's rows bitwise as they were."""
    k = jax.random.split(jax.random.key(0), 7)
    lm, b, h, p, n = 3, 4, 8, 16, 32
    state = jax.random.normal(k[0], (lm, b, h, p, n), jnp.float32)
    x = jax.random.normal(k[1], (b, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (b, h)))
    A = -jnp.exp(jax.random.normal(k[3], (h,)))
    B = jax.random.normal(k[4], (b, n), jnp.bfloat16)
    C = jax.random.normal(k[5], (b, n), jnp.bfloat16)
    D = jax.random.normal(k[6], (h,))
    mask = jnp.asarray([True, False, True, True])
    y0, s0 = ssm_ref.ssm_decode(state, 1, x, dt, A, B, C, D, mask)
    y1, s1 = jax.jit(lambda s: ssm_decode_kernel(
        s, 1, x, dt, A, B, C, D, mask, interpret=True))(state)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    assert bool((s1[1, 1] == state[1, 1]).all())
    assert bool((s1[0] == state[0]).all()) and bool((s1[2] == state[2]).all())
    assert not bool((s1[1, 0] == state[1, 0]).all())


def _engine(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 40)
    kw.setdefault("page_size", 8)
    return make_engine(ARCH, **kw)


def _served(reqs, **kw):
    eng, clock = _engine(**kw)
    Simulator(eng, burst_trace(reqs), clock).run()
    return eng, tokens_of(eng)


def test_the_hybrid_takes_the_paged_backend_with_two_arenas():
    cfg = configs.smoke(ARCH)
    eng, _ = _engine()
    assert registry.supports_paged(cfg) and registry.holds_state(cfg)
    assert eng.stats()["backend"] == "paged" and eng.pages is None
    pages = eng._pool.n_pages + 1
    # the KV arena holds the attention layers' pages only
    assert eng._arena.k.shape[0] == hybrid.n_attention(cfg) * pages
    assert hybrid.n_attention(cfg) < cfg.n_layers
    st = eng._state
    assert st.state.dtype == jnp.float32
    assert st.state.shape == (hybrid.n_mamba(cfg), eng.n_lanes,
                              cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    assert st.conv_x.shape[:3] == (hybrid.n_mamba(cfg),
                                   cfg.ssm_conv_width - 1, eng.n_lanes)


def test_engine_tokens_are_the_argmax_of_the_paged_logits():
    cfg, params = smoke_params(ARCH)
    req = make_requests(1, prompt_len=6, new_tokens=8)[0]
    _, toks = _served([req])
    seq = list(req.prompt) + list(toks["r0"])
    logits = _paged_logits(cfg, params, seq[:-1])
    assert [int(t) for t in logits[len(req.prompt) - 1:].argmax(-1)] == \
        list(toks["r0"])


def test_a_reused_slot_gives_the_tokens_served_alone():
    a, b = make_requests(2, prompt_len=5, new_tokens=7)
    _, alone = _served([dataclasses.replace(b, tokens=[])], slots=1)
    eng, both = _served([a, b], slots=1)
    assert eng.steps > len(b.prompt) + b.max_new_tokens   # b reused a's slot
    assert both["r1"] == alone["r1"]


@pytest.mark.parametrize("async_dispatch", [False, True])
def test_preemption_and_replay_give_the_same_greedy_stream(async_dispatch):
    reqs = make_requests(3, prompt_len=6, new_tokens=9)
    _, want = _served([dataclasses.replace(r, tokens=[]) for r in reqs])
    eng, _ = _engine(async_dispatch=async_dispatch)
    for r in reqs:
        eng.submit(r)
    for _ in range(8):
        eng.step()
    assert eng.preempt()
    eng.run_until_idle()
    assert tokens_of(eng) == want


def test_a_shared_prefix_is_never_adopted():
    prefix = [11 + i for i in range(20)]
    reqs = [Request(f"s{i}", prefix + [200 + i, 3], 6) for i in range(2)]
    alone = {r.id: _served([dataclasses.replace(r, tokens=[])])[1][r.id]
             for r in reqs}
    eng, clock = _engine()
    for r in reqs:
        eng.submit(r)
    while eng.step():
        live = [set(s.pages_by_block.values()) for s in eng.slots if s]
        if len(live) == 2:
            assert not live[0] & live[1]
    assert eng.stats()["prompt_tokens_reused"] == 0
    assert tokens_of(eng) == alone


def test_what_the_hybrid_cannot_do_is_refused():
    cfg, params = smoke_params(ARCH)
    with pytest.raises(ValueError, match="prefill_chunk > 1"):
        ContinuousBatchingEngine(cfg, params, slots=2, max_len=40,
                                 page_size=8, prefill_chunk=2)
    with pytest.raises(ValueError, match="cannot adopt prefixes"):
        ContinuousBatchingEngine(cfg, params, slots=2, max_len=40,
                                 page_size=8, page_table=PageTable(8),
                                 pool=paged.PagePool(32, 8))
    R.validate_serve_tp(cfg, 1)
    with pytest.raises(ValueError, match="cannot serve tensor-parallel"):
        R.validate_serve_tp(cfg, 2)


def test_admission_zeroes_the_slot_state_and_counts_it():
    t0 = tracing.steps()[-1].start if tracing.steps() else None
    eng, _ = _served(make_requests(3, prompt_len=4, new_tokens=5))
    logged = [t for t in tracing.steps(eng.name, since=t0)
              if t0 is None or t.start > t0]
    assert sum(t.state_resets for t in logged) == 3
    assert sum(t.admitted for t in logged) == 3
    assert all("engine.state_reset" in t.phases
               for t in logged if t.state_resets)
