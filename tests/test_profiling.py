"""Profiling hooks (SURVEY.md section 5 tracing equivalent)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.utils import profiling


def test_trace_writes_profile(tmp_path):
    logdir = str(tmp_path / "trace")
    with obs.run_scope(str(tmp_path / "run")), profiling.trace(logdir):
        with obs.trace.span("work"):
            x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
            np.asarray(x)
    found = [os.path.join(r, f) for r, _d, fs in os.walk(logdir) for f in fs]
    assert found, "profiler produced no trace files"


def test_round_timer_accumulates():
    t = profiling.RoundTimer()
    with t.phase("solve", sync_fn=lambda: jnp.ones(4)):
        jnp.ones(8)
    t.start("exchange")
    t.stop("exchange")
    with t.phase("solve"):
        pass
    assert t.counts == {"solve": 2, "exchange": 1}
    assert all(v >= 0.0 for v in t.totals.values())
    s = t.summary()
    assert "solve" in s and "exchange" in s


def test_round_timer_nested_phases():
    """Distinct phases nest freely; the inner window is contained in the
    outer's accumulated time."""
    t = profiling.RoundTimer()
    with t.phase("outer"):
        with t.phase("inner"):
            pass
    assert t.counts == {"outer": 1, "inner": 1}
    assert t.totals["outer"] >= t.totals["inner"] >= 0.0
    # Re-entering the same phase name while it is open: the second start()
    # overwrites the mark (one open window per name), and the single stop
    # closes it — counted once, no dangling mark.
    t2 = profiling.RoundTimer()
    t2.start("p")
    t2.start("p")
    t2.stop("p")
    assert t2.counts["p"] == 1
    with pytest.raises(ValueError):
        t2.stop("p")  # the overwritten mark is gone


def test_round_timer_stop_without_start_raises():
    t = profiling.RoundTimer()
    with pytest.raises(ValueError, match="without a matching start"):
        t.stop("never_started")
    # The error names the phases that ARE open — the actionable detail
    # when a phase string is mistyped mid-refactor.
    t.start("solve")
    with pytest.raises(ValueError, match=r"open phases: solve"):
        t.stop("slove")
    assert "solve" in t._t0  # the open window survives the failed stop


def test_round_timer_stop_guard_precedes_sync():
    """A never-started stop must fail fast WITHOUT materializing the sync
    value — no device->host transfer paid for a window that never
    opened."""

    class Probe:
        materialized = False

        def __array__(self, dtype=None, copy=None):
            Probe.materialized = True
            return np.zeros(1)

    t = profiling.RoundTimer()
    with pytest.raises(ValueError, match="without a matching start"):
        t.stop("never_started", sync=Probe())
    assert not Probe.materialized


def test_round_timer_sync_fence_materializes_device_value():
    """``stop(sync=x)`` must force a device->host materialization."""

    class Probe:
        materialized = False

        def __array__(self, dtype=None, copy=None):
            Probe.materialized = True
            return np.zeros(1)

    t = profiling.RoundTimer()
    t.start("solve")
    t.stop("solve", sync=Probe())
    assert Probe.materialized, "sync value was not materialized"
    # And a real device value round-trips without error.
    t.start("solve")
    dt = t.stop("solve", sync=jnp.arange(8.0) * 2.0)
    assert dt >= 0.0


def test_round_timer_as_dict_and_reset():
    t = profiling.RoundTimer()
    with t.phase("solve"):
        pass
    with t.phase("solve"):
        pass
    t.start("exchange")
    t.stop("exchange")
    d = t.as_dict()
    assert set(d) == {"solve", "exchange"}
    assert d["solve"]["count"] == 2
    assert d["solve"]["total_s"] == pytest.approx(t.totals["solve"])
    assert d["solve"]["avg_ms"] == pytest.approx(
        1e3 * t.totals["solve"] / 2)
    # as_dict is a snapshot payload (JSON-ready plain types).
    import json

    json.dumps(d)

    t.start("open")  # in-flight mark must be dropped by reset too
    t.reset()
    assert t.totals == {} and t.counts == {}
    with pytest.raises(ValueError):
        t.stop("open")
    # Reusable after reset.
    with t.phase("solve"):
        pass
    assert t.counts == {"solve": 1}
