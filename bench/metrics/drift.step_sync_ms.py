"""Time a step waited on the device, in ms: summed duration of the
``repartition.sync`` spans (blocking device->host reads) nested inside
a ``repartition.step`` span, per step span of the window."""
import bisect


def read(run):
    prof = run.profile
    if prof is None:
        return None
    lo, hi = prof.window
    spans = [sp for sp in prof.spans if lo <= sp[1] < hi]
    steps = sorted((s, e) for name, s, e in spans if name == "repartition.step")
    if not steps:
        return None
    starts = [s for s, _ in steps]

    def in_step(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= steps[i][1]

    return 1e-6 * sum(e - s for name, s, e in spans
                      if name == "repartition.sync" and in_step(s, e)) / len(steps)
