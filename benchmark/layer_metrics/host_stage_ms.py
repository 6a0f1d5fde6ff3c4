"""Host milliseconds a round spent staging (cohort sampling, index maps,
device_put): the ``engine/stage`` spans of the window over its rounds. The
staging thread overlaps the device, so this is host work, not stall."""


def read(ctx):
    spans = [s for s in ctx["host_spans"] if s["name"] == "engine/stage"]
    if not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx["window"]["rounds"]
