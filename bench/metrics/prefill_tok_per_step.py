"""Engine: mean prompt tokens fed per window step, counted by the program
(``prompt_fed`` in its step log, ``bench/spans.py``)."""

from bench import spans


def read(run, variant):
    steps = spans.window(run)
    if steps is None:
        return None
    return sum(t.prompt_fed for t in steps) / len(steps)
