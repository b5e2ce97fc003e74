"""CPU rehearsal of each cell at the tiny sizes of bench/rehearsal.json,
and the same run with the timed path broken underneath: `correct` must
come out false for every fault the cell can have. (The exchange between
chips is absent: the cell runs on one chip.)"""
import numpy as np
import pytest

from bench import run

CELLS = ["bertmini2pc.probe"]


def _run(workload, seed=11):
    return run.run_cell(workload, seed, 0.0, False, rehearse=True,
                        log=lambda *a, **k: None)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["candidates_per_s"]["value"] > 0


def _alter_answer(monkeypatch, PhaseRun, Share):
    """One candidate's score altered where it is produced."""
    orig = PhaseRun.finish

    def finish(self):
        ent, rep = orig(self)
        sh = np.asarray(ent.sh).copy()
        sh[0, 0] += np.int32(1 << self.ring.frac_bits)
        return Share(sh, ent.ring, ent.proto, ent.fb), rep
    monkeypatch.setattr(PhaseRun, "finish", finish)


def _half_batch(monkeypatch, PhaseRun, Share):
    """Half of every batch left out: its scores are the other half's."""
    orig = PhaseRun.finish

    def finish(self):
        ent, rep = orig(self)
        sh = np.asarray(ent.sh).copy()
        h = self.B // 2
        for b0 in range(0, sh.shape[1] - self.B + 1, self.B):
            sh[:, b0 + h:b0 + 2 * h] = sh[:, b0:b0 + h]
        return Share(sh, ent.ring, ent.proto, ent.fb), rep
    monkeypatch.setattr(PhaseRun, "finish", finish)


def _stale_wave(monkeypatch, PhaseRun, Share):
    """A step that returns its state unchanged: every wave after the
    first hands back the previous wave's scores."""
    orig = PhaseRun.dispatch

    def dispatch(self, wi):
        orig(self, wi)
        if wi > 0 and self.results[-1].shape == self.results[-2].shape:
            self.results[-1] = self.results[-2]
    monkeypatch.setattr(PhaseRun, "dispatch", dispatch)


FAULTS = {"alter_answer": _alter_answer, "half_batch": _half_batch,
          "stale_wave": _stale_wave}
CASES = [(w, f) for w in CELLS for f in FAULTS]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    from repro.core.executor import PhaseRun
    from repro.mpc.sharing import Share
    FAULTS[fault](monkeypatch, PhaseRun, Share)
    res = _run(workload)
    assert not res["correct"], res["checks"]
