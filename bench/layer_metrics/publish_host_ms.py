"""The self time of a snapshot publication: ``snapshot.build`` less the
``topk.dispatch`` and ``topk.readback`` spans inside it, per
publication, over the window.  The host's Python of the publication:
route walk, caches and the assembly of each head."""


def read(run):
    n, build = run.spans.get("snapshot.build", (0, 0.0))
    if not n or not {"topk.dispatch", "topk.readback"} <= set(run.spans):
        return None
    inner = run.spans["topk.dispatch"][1] + run.spans["topk.readback"][1]
    return (build - inner) / n * 1e3
