"""Share of the traced window in which a chip ran no operation, in %,
averaged over the four chips: 1 - (union of its XLA Ops intervals) /
window, from the device planes."""


def read(run):
    return 100.0 * run.profile.idle_share() if run.profile and run.profile.ops else None
