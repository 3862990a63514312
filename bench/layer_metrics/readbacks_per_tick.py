"""``topk.readback`` spans per snapshot publication over the window: the
blocking head readbacks a tick makes (one span per live selection's
head, its two transfers inside it)."""


def read(run):
    n, _ = run.spans.get("snapshot.build", (0, 0.0))
    if not n or "topk.readback" not in run.spans:
        return None
    return run.spans["topk.readback"][0] / n
