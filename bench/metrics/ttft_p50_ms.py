"""Median time to first token over the requests due in the window."""

from bench.stats import percentile, ttft_ms


def read(run, variant):
    v = ttft_ms(run)
    return percentile(v, 50) if v else None
