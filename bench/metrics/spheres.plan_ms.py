"""Summed duration of the program's ``mesh.plan`` spans (the halo and move plan build on the host)
per ``mesh.timestep`` span of the window, in ms (program spans, read
from the profiler trace on the device's clock)."""


def read(run):
    prof = run.profile
    if prof is None:
        return None
    lo, hi = prof.window
    spans = [sp for sp in prof.spans if lo <= sp[1] < hi]
    steps = sum(1 for name, _, _ in spans if name == "mesh.timestep")
    if not steps:
        return None
    return 1e-6 * sum(e - s for name, s, e in spans if name == "mesh.plan") / steps
