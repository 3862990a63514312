"""Mean of the program's ``ingest.apply`` span over the window: one
tick's records through ``SelectionService.ingest``, from the store's
write through the fleet's ingest dispatch (no sync)."""


def read(run):
    mean = run.span_mean("ingest.apply")
    return None if mean is None else mean * 1e3
