"""Share of its memory roofline that the V-wide stencil kernel reaches,
in %: the HBM bytes its own operands move (``kernel_bytes``) over the
chip's peak bandwidth (``peaks``), against the summed device time of
its events (``stencil_update_v`` in the trace, every chip)."""
import kernel_bytes
import peaks


def read(run):
    prof = run.profile
    if prof is None or not prof.ops:
        return None
    ops = prof.kernel_ops(kernel_bytes.STENCIL_UPDATE_V)
    if not ops:
        return None
    moved = [kernel_bytes.stencil_update_v(o.name) for o in ops]
    if None in moved:
        return None
    bw = peaks.peak(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * (sum(moved) / bw) / (1e-9 * sum(o.dur for o in ops))
