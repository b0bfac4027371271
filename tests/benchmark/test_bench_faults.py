"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run on the CPU (no chip look) with one fault
planted in the program's serving path, and the same comparison and limit
as the sound run in ``test_bench_control.py``:

* the step returns its KV state unchanged (the append is dropped);
* a served token is altered where the engine hands it out;
* each query attends over only the first half of its context.

The exchange between chips does not exist on one chip.
"""

import pytest

from smallcell import run, small_cell
from test_bench_control import LIMIT


class _AlterThirdToken:
    """The engine's fault hook: every request's third token is replaced."""

    def __init__(self, vocab):
        self.vocab = vocab
        self.n = 0

    def launch(self, name):
        return None

    def deliver_token(self, name, tok):
        self.n += 1
        return (tok + 1) % self.vocab if self.n % 3 == 0 else tok


def _state_unchanged(monkeypatch, engine):
    from repro.kernels.paged_attention import ops, ref

    def no_append(q, k_new, v_new, k_pool, v_pool, tables, lengths, **kw):
        o = ref.paged_attention(q, k_pool, v_pool, tables, lengths + 1,
                                window=kw.get("window"))
        return o, k_pool, v_pool

    monkeypatch.setattr(ops, "paged_decode_append", no_append)


def _token_altered(monkeypatch, engine):
    engine.chaos = _AlterThirdToken(engine.cfg.vocab)


def _half_context(monkeypatch, engine):
    from repro.kernels.paged_attention import ref

    whole = ref.paged_attention

    def half(q, k_pool, v_pool, tables, lengths, **kw):
        return whole(q, k_pool, v_pool, tables, (lengths + 1) // 2, **kw)

    monkeypatch.setattr(ref, "paged_attention", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_context])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    cell = small_cell(f"fault{fault.__name__}", "open", LIMIT)
    _, out = run(cell, engine_hook=lambda e: fault(monkeypatch, e))
    assert out["checks"]["tokens_compared"]["value"] > 0
    assert not out["correct"], out["checks"]
