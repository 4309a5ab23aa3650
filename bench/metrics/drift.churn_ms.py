"""Mean wall time of ``delete`` + ``insert`` per churn step of the
window (benchmark span ``drift.churn``, host clock)."""


def read(run):
    t = run.layer.get("churn_s")
    return 1e3 * sum(t) / len(t) if t else None
