"""The comparison that decides ``correct``: served tokens against the
float32 reference.

Once the window has closed and the engine is freed, a sample of the
requests the run finished — the one with the most served tokens, then
others drawn from the seed until ``check_tokens`` served tokens are in
it — is run through ``bench.reference`` over its prompt and its served
tokens. The number compared is the widest gap by which a served token's
logit lies below the reference's best at its position; the cell's limit
file (``bench/limits/<cell>.json``) holds its limit and the readings the
limit was set from.
"""

from __future__ import annotations

from bench import reference, spec


def sample(finished: list, check_tokens: int, rng) -> list:
    """The longest finished request, then others in seeded order until
    ``check_tokens`` served tokens are covered."""
    if not finished:
        return []
    ordered = sorted(finished, key=lambda r: (-len(r.tokens), r.id))
    chosen, rest = [ordered[0]], ordered[1:]
    n = len(ordered[0].tokens)
    for i in rng.permutation(len(rest)):
        if n >= check_tokens:
            break
        chosen.append(rest[i])
        n += len(rest[i].tokens)
    return chosen


def check(cell, params, finished: list, seed: int, log, control: bool = False
          ) -> dict:
    """Compare; returns ``correct``, the readings and the limits."""
    cfg = cell.config
    vocab, max_len = cfg["vocab_size"], cfg["engine"]["max_len"]
    limit = cell.limits["max_logit_gap"]
    chosen = sample(finished, cell.traffic["check_tokens"],
                    spec.rng(seed, "check"))
    gaps, low = [], []
    out_of_vocab = 0
    for r in chosen:
        out_of_vocab += sum(not 0 <= t < vocab for t in r.tokens)
        gaps += reference.served_gaps(cfg, params, r.prompt, r.tokens, max_len)
        if control:
            low += reference.control_gaps(cfg, params, r.prompt, r.tokens,
                                          max_len)
    reading = max(gaps) if gaps else float("nan")
    checks = {
        "max_logit_gap": {"value": reading, "limit": limit},
        "tokens_compared": {"value": len(gaps), "limit": 1},
        "tokens_out_of_vocab": {"value": out_of_vocab, "limit": 0},
    }
    correct = (limit is not None and bool(gaps) and reading <= limit
               and out_of_vocab == 0)
    out = {"correct": correct, "checks": checks,
           "requests_compared": len(chosen)}
    if control:
        out["control_gap"] = max(low) if low else float("nan")
    return out
