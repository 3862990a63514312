"""The program's ``topk.readback`` spans over the window, per snapshot
publication (``snapshot.build``): the blocking transfers of every live
selection's head to the host."""


def read(run):
    n, _ = run.spans.get("snapshot.build", (0, 0.0))
    _, total = run.spans.get("topk.readback", (0, 0.0))
    if not n or "topk.readback" not in run.spans:
        return None
    return total / n * 1e3
