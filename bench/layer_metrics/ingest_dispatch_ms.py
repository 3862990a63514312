"""Mean of the program's ``ingest.dispatch`` span over the window: the
enqueue of the fleet's jitted ingest step (its operands' transfers
included, no sync), the part of ``ingest.apply`` that reaches the
device."""


def read(run):
    mean = run.span_mean("ingest.dispatch")
    return None if mean is None else mean * 1e3
