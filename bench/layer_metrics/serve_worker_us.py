"""Mean of the program's ``serve.worker`` span (sampled 1 in 32 per
worker) over the window: the front end's lock-free serve."""


def read(run):
    mean = run.span_mean("serve.worker")
    return None if mean is None else mean * 1e6
