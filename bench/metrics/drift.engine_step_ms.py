"""Mean wall time of ``Repartitioner.step()`` per step of the window,
through ``block_until_ready`` of its ``part`` (benchmark span
``drift.engine_step``, host clock)."""


def read(run):
    t = run.layer.get("engine_step_s")
    return 1e3 * sum(t) / len(t) if t else None
