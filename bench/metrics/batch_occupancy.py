"""Engine: mean number of slots handed a token per step, over the steps
of the window."""


def read(run, variant):
    steps = run.window_steps()
    return sum(s.emitted for s in steps) / len(steps) if steps else None
