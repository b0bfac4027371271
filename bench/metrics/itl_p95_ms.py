"""95th percentile (nearest rank) of every gap between consecutive output
tokens of every request, over the gaps that open inside the window."""

from bench.stats import gaps_ms, percentile


def read(run, variant):
    v = gaps_ms(run)
    return percentile(v, 95) if v else None
