"""Share of the traced window in which no operation ran on the device."""


def read(run):
    if run.summary is None:
        return None
    return run.summary.idle_share * 100.0
