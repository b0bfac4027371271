"""Set-up: process start to the window's start, compilation included."""


def read(run, variant):
    return run.setup_s
