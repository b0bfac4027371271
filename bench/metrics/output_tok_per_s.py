"""Output tokens handed out in the window over the window's seconds."""

from bench.stats import tokens_in


def read(run, variant):
    span = run.t1 - run.t0
    return tokens_in(run, (run.t0, run.t1)) / span if span > 0 else None
