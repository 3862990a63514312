"""Mean of the program's ``snapshot.build`` span over the window: one
top-k head per live selection, from the device."""


def read(run):
    mean = run.span_mean("snapshot.build")
    return None if mean is None else mean * 1e3
