"""The closed forms the roofline and MFU rest on, at smoke widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, reference, spec, weights
from bench.kernels import paged_attn


def small(name, **kw):
    cfg = spec.load_json(spec.ROOT / "bench" / "configs" / f"{name}.json")
    return dict(cfg, num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                intermediate_size=96, vocab_size=256,
                attention_multiplier=16 ** -0.5, **kw)


CONFIGS = [small("granite-3-2b", num_key_value_heads=2),
           small("stablelm-3b", num_key_value_heads=4)]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_matmul_weights_are_the_weights_a_token_multiplies(cfg):
    shapes = weights.shapes(cfg)
    want = sum(int(np.prod(s)) for p, (s, init) in shapes.items()
               if isinstance(init, int))
    if cfg["tie_word_embeddings"]:
        want += int(np.prod(shapes["embed"][0]))     # the head reuses it
    assert flops.matmul_weights(cfg) == want


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_token_flops_match_xla_count_of_the_reference(cfg):
    """XLA's count of the reference's forward over S tokens (causal
    attention computed in full: S·S score positions) against the closed
    form; the difference is elementwise work, under 3% at this width.
    One layer, since XLA counts a loop's body once."""
    cfg = dict(cfg, num_hidden_layers=1)
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
    hd = spec.head_dim(cfg)
    rows = 0
    for _ in range(cfg["num_hidden_layers"]):
        for n in lengths:
            rows += 2 * n * cfg["num_key_value_heads"]          # K and V
            rows += 2 * cfg["num_attention_heads"]              # q and o
    f, b = paged_attn.cost(cfg, len(lengths), sum(lengths))
    assert b == rows * hd * 2
    assert f == 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * hd * sum(lengths)
