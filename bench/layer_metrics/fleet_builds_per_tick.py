"""Fleet universes built over the window, per tick published in it: the
program's ``rank.cold_rebuilds`` counter (every cold build of the fleet,
a fallback's included).  0 when every record lands through the ingest
step."""


def read(run):
    counters = getattr(run, "counters", {})
    if not run.attempted or "rank.cold_rebuilds" not in counters:
        return None
    return counters["rank.cold_rebuilds"] / run.attempted
