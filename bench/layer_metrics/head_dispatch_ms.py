"""The program's ``topk.dispatch`` spans over the window, per snapshot
publication (``snapshot.build``): the enqueue of every live selection's
head (slot lookup, slices, the jitted ``top_k``)."""


def read(run):
    n, _ = run.spans.get("snapshot.build", (0, 0.0))
    _, total = run.spans.get("topk.dispatch", (0, 0.0))
    if not n or "topk.dispatch" not in run.spans:
        return None
    return total / n * 1e3
