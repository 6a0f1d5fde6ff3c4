"""Peak bytes on the fullest chip after the window, in GB: live arrays at
their peak plus the scratch XLA reserves for the loaded programs
(``benchmark.run.peak_bytes``). A per-layer metric until a later benchmark PR
makes it an end-to-end one."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9 if ctx["memory_peak_bytes"] else None
