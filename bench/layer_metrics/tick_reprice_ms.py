"""Mean of the program's ``tick.reprice`` span over the window: price
apply, validation and the fleet's reprice dispatch up to its sync."""


def read(run):
    mean = run.span_mean("tick.reprice")
    return None if mean is None else mean * 1e3
