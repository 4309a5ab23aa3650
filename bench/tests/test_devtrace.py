"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e chip (bench/tests/data)."""
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29), (40, 50)]
    assert devtrace.union_ns(iv, 0, 60) == 15 + 10 + 10
    assert devtrace.union_ns(iv, 8, 45) == 7 + 10 + 5
    assert devtrace.gaps_ns(iv, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert devtrace.gaps_ns(iv, 12, 25) == [(15, 20)]
    assert devtrace.union_ns([], 0, 5) == 0 and devtrace.gaps_ns([], 0, 5) == [(0, 5)]


def _plain_busy(ops, lo, hi):
    """Busy time by marking each nanosecond-interval endpoint: a second,
    independent union (sweep over sorted endpoints)."""
    pts = sorted({lo, hi, *[min(max(t, lo), hi) for o in ops for t in (o.start, o.start + o.dur)]})
    busy = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(o.start <= mid < o.start + o.dur for o in ops):
            busy += b - a
    return busy


@pytest.mark.skipif(not DATA.exists(), reason="recorded trace not present")
def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    prof = devtrace.Profile.from_data(ProfileData.from_file(str(DATA)))
    assert list(prof.ops) == ["/device:TPU:0"]
    ops = prof.ops["/device:TPU:0"]
    lo, hi = prof.window
    assert ops and all(lo - 1e9 < o.start < hi + 1e9 for o in ops)
    busy = prof.busy_ns("/device:TPU:0")
    assert busy == pytest.approx(_plain_busy(ops, lo, hi))
    assert 0 < busy < hi - lo
    assert prof.idle_share() == pytest.approx(1 - busy / (hi - lo))
    assert prof.busy_s(1) == pytest.approx(busy * 1e-9)
    bd = prof.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    idle = sum(s for _, s in bd["idle_gaps"])
    assert idle == pytest.approx((hi - lo - busy) * 1e-9)
    assert {name for name, _ in bd["idle_gaps"]} <= {"probe.host_wait", "probe.step", "no span"}
    assert "probe.host_wait" in dict(bd["idle_gaps"])
