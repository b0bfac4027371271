"""The closed forms the roofline and MFU rest on, for every configuration
the benchmark lists, each cut to its model file's ``small`` size; and
the closed forms at the published sizes, pinned to their values."""

import json
import math

import jax
import jax.numpy as jnp
import pytest

from bench import flops, reference, spec, weights
from bench.kernels import paged_attn


def _config(name):
    return spec.load_json(spec.ROOT / "bench" / "configs" / f"{name}.json")


NAMES = [c["name"] for c in
         json.loads((spec.ROOT / "BENCHMARK.json").read_text())["configs"]]
CONFIGS = [spec.model(_config(n)).small(_config(n)) for n in NAMES]


def _dot_flops(jaxpr) -> int:
    """FLOPs of every ``dot_general`` in ``jaxpr``, a scan's body counted
    once for each of its iterations."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            total += 2 * math.prod(out) * math.prod(lhs[i] for i in lc)
        if eqn.primitive.name == "while":
            raise ValueError("a while loop's trip count is not in its jaxpr")
        times = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, tuple) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += times * _dot_flops(inner)
    return total


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_matmul_weights_are_the_weights_a_token_multiplies(cfg):
    """Every matrix product of the reference's forward over one token
    (which attends over itself alone) is two FLOPs a weight it multiplies,
    plus the scores and weighted sum of each layer that holds KV; and no
    more weights than the tree's matrices (fan-in leaves, and a tied
    embedding that the head reuses)."""
    params = weights.make(cfg, 0, jnp.float32)
    tokens = jnp.zeros((1,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: reference.logits(cfg, p, t))(params, tokens)
    attn = 4 * sum(h * hd for h, _, hd in spec.model(cfg).attention_layers(cfg))
    assert _dot_flops(jaxpr.jaxpr) == 2 * flops.matmul_weights(cfg) + attn
    shapes = weights.shapes(cfg)
    matrices = sum(math.prod(s) for s, init in shapes.values()
                   if isinstance(init, int))
    if cfg["tie_word_embeddings"]:
        matrices += math.prod(shapes["embed"][0])
    assert flops.matmul_weights(cfg) <= matrices


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_token_flops_match_xla_count_of_the_reference(cfg, monkeypatch):
    """XLA's count of the reference's forward over S tokens (causal
    attention computed in full: S·S score positions) against the closed
    form; the difference is elementwise work, under 3% at this width.
    Every scan is unrolled, since XLA counts a loop's body once."""
    scan = jax.lax.scan
    monkeypatch.setattr(jax.lax, "scan",
                        lambda *a, **kw: scan(*a, **dict(kw, unroll=True)))
    params = weights.make(cfg, 0, jnp.float32)
    s = 16
    tokens = jnp.zeros((s,), jnp.int32)
    lowered = jax.jit(lambda p, t: reference.logits(cfg, p, t)).lower(params, tokens)
    xla = lowered.compile().cost_analysis()["flops"]
    want = flops.tokens_flops(cfg, s, s * s)
    assert want == pytest.approx(xla, rel=0.03)


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_kernel_bytes_count_live_tokens_at_logical_width(cfg):
    """Enumerate, slot by slot and layer by layer, the K and V rows each
    query reads and the q and o it moves."""
    lengths = [1, 17, 64, 5]
    nbytes = nflops = 0
    for heads, kv_heads, hd in spec.model(cfg).attention_layers(cfg):
        for n in lengths:
            nbytes += 2 * n * kv_heads * hd * 2       # K and V
            nbytes += 2 * heads * hd * 2              # q and o
            nflops += 4 * heads * hd * n
    f, b = paged_attn.cost(cfg, len(lengths), sum(lengths))
    assert b == nbytes
    assert f == nflops


# (queries, attended) -> (tokens_flops, paged_attn.cost) of the code
# before the equations moved into bench/models/, at the published sizes
PINNED = {
    "granite-3-2b": (2533365760, {
        (1, 1): (5067059200.0, (327680.0, 409600.0)),
        (32, 12345): (166180618240.0, (4045209600.0, 1021788160.0)),
        (8, 4321): (41949757440.0, (1415905280.0, 356597760.0)),
        (26, 19000): (137960939520.0, (6225920000.0, 1564999680.0))}),
    "stablelm-3b": (2666332160, {
        (1, 1): (5332992000.0, (327680.0, 655360.0)),
        (32, 12345): (174690467840.0, (4045209600.0, 4055695360.0)),
        (8, 4321): (44077219840.0, (1415905280.0, 1418526720.0)),
        (26, 19000): (144875192320.0, (6225920000.0, 6234439680.0))}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_closed_forms_keep_their_values_through_the_model_file(name):
    cfg = _config(name)
    weights_, points = PINNED[name]
    assert flops.matmul_weights(cfg) == weights_
    for (q, a), (tok, cost) in points.items():
        assert flops.tokens_flops(cfg, q, a) == tok
        assert paged_attn.cost(cfg, q, a) == cost
