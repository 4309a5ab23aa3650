"""Share of the traced window in which the chip ran no operation, in %:
1 - (union of its XLA Ops intervals) / window, from the device plane."""


def read(run):
    return 100.0 * run.profile.idle_share() if run.profile and run.profile.ops else None
