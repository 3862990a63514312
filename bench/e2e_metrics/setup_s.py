"""Set-up: process start to the window's start (data, store, fleet, warm-up)."""


def read(run):
    return run.setup_s
