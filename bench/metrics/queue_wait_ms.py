"""Engine: due time to admission (``Request.admit_time``), at the
percentile the variant names (``p90``), over requests due in the window."""

from bench.stats import percentile


def read(run, variant):
    q = float(variant.lstrip("p"))
    v = [(r.request.admit_time - r.due) * 1e3 for r in run.reqs
         if r.in_window and r.request is not None
         and r.request.admit_time is not None]
    return percentile(v, q) if v else None
