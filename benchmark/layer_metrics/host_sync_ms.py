"""Host milliseconds a round spent waiting on the device: the ``engine/sync``
(metrics fetch at an eval boundary) and ``engine/eval`` spans of the window
over its rounds."""


def read(ctx):
    spans = [s for s in ctx["host_spans"] if s["name"] in ("engine/sync", "engine/eval")]
    if not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx["window"]["rounds"]
