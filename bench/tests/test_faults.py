"""Every fault a cell can have, planted under the timed path at the tiny
size on the CPU, makes the run's ``correct`` false: the comparison is
shown to fail.  The look for the chip is the only part of a run skipped."""
import numpy as np
import pytest

from conftest import run_tiny

DECODE, SERVE, MIGRATE = ("glm4-9b.decode-8k", "granite-3b-a800m.serve-2k",
                          "glm4-9b.migrate-8k")
ALL = [DECODE, SERVE, MIGRATE]
SERVE_TRAFFIC = {"rate_per_s": 25.0, "grace_s": 2.0}


def _traffic(workload):
    return SERVE_TRAFFIC if workload == SERVE else {}


def _finish_with(monkeypatch, alter):
    from repro.core.runtime import LaunchRecord

    real = LaunchRecord._finish

    def finish(self):
        before = self.bindings["O"].data.copy()
        real(self)
        alter(self.bindings["O"].data, before)

    monkeypatch.setattr(LaunchRecord, "_finish", finish)


@pytest.mark.parametrize("workload", ALL)
def test_answer_altered_where_produced(monkeypatch, bench_spec, workload):
    def alter(o, _before):
        o[len(o) // 2] += 1e-3 * np.abs(o).max()
    _finish_with(monkeypatch, alter)
    res = run_tiny(monkeypatch, bench_spec, workload,
                   traffic=_traffic(workload))
    assert res["correct"] is False
    assert res["compared"]["o_err_max"]["value"] > \
        res["compared"]["o_err_max"]["limit"]


@pytest.mark.parametrize("workload", ALL)
def test_launch_leaves_its_output_unchanged(monkeypatch, bench_spec,
                                            workload):
    def keep(o, before):
        o[:] = before
    _finish_with(monkeypatch, keep)
    res = run_tiny(monkeypatch, bench_spec, workload,
                   traffic=_traffic(workload))
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ALL)
def test_half_the_heads_left_out(monkeypatch, bench_spec, workload):
    from repro.core.runtime import Function

    real = Function.launch_async

    def half(self, grid, block, args, stream=None):
        return real(self, max(1, grid // 2), block, args, stream=stream)

    monkeypatch.setattr(Function, "launch_async", half)
    res = run_tiny(monkeypatch, bench_spec, workload,
                   traffic=_traffic(workload))
    assert res["correct"] is False


def test_migration_loses_the_paused_registers(monkeypatch, bench_spec):
    """State capture drops the softmax state (running max, sum and the
    accumulators) of a paused launch."""
    from repro.core.state import Snapshot

    real = Snapshot.to_bytes

    def to_bytes(self):
        self.regs = {k: (np.zeros_like(v) if np.asarray(v).dtype.kind == "f"
                         else v) for k, v in self.regs.items()}
        return real(self)

    monkeypatch.setattr(Snapshot, "to_bytes", to_bytes)
    res = run_tiny(monkeypatch, bench_spec, MIGRATE)
    assert res["correct"] is False


def test_served_request_that_never_completes(monkeypatch, bench_spec):
    from repro.core.serving import ServingFrontEnd

    real = ServingFrontEnd.submit
    count = {"n": 0}

    def submit(self, *a, **kw):
        ticket = real(self, *a, **kw)
        count["n"] += 1
        if count["n"] == 3:          # the second request of the window
            ticket.record.cancel()
        return ticket

    monkeypatch.setattr(ServingFrontEnd, "submit", submit)
    res = run_tiny(monkeypatch, bench_spec, SERVE, traffic=SERVE_TRAFFIC)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert res["compared"]["unanswered"]["value"] >= 1
