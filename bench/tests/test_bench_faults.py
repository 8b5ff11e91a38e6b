"""The check has to catch a broken program: each fault a cell can have is
planted under a whole run (tiny sizes, on the CPU, with the chip check
skipped) and the run has to come out not correct. The control, the
reference in bfloat16 in the program's place, has to fail too.

Both cells solve one right-hand side on one unit, so the faults are a
state left unchanged and an answer altered where it is produced; no
batch is there to halve and no exchange to leave out."""
import dataclasses

import numpy as np
import pytest
from tiny import tiny_cell

from harness.cell import run

DIRECT = ["hpcg104.cg-direct", "g500s15.pr-direct"]


def one_run(name, **kw):
    cell = tiny_cell(name)
    return run(cell, seed=2**31 + 99, seconds=0.3, traced=False, compile_cache=False, **kw)


@pytest.mark.parametrize("name", DIRECT)
def test_sound_run_is_correct(name):
    result = one_run(name)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"solve_s", "setup_s"}


@pytest.mark.parametrize("name", DIRECT)
def test_control_is_not_correct(name):
    result = one_run(name, control=True)
    assert not result["correct"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def identity_spmv(self, x, *, executor=None):
    return np.array(x, dtype=np.float32)


def identity_device_spmm(self):
    return lambda x: x


def altered_result(orig):
    def result(*args, **kw):
        res = orig(*args, **kw)
        x = res.x.copy()
        x.flat[np.argmax(np.abs(x))] *= 2.0
        return dataclasses.replace(res, x=x)

    return result


@pytest.mark.parametrize("name", DIRECT)
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_direct_faults_are_caught(name, fault, monkeypatch):
    from repro.api import session, solvers

    if fault == "state_unchanged":
        monkeypatch.setattr(session.SparseSession, "spmv", identity_spmv)
        monkeypatch.setattr(session.SparseSession, "device_spmm", identity_device_spmm)
    else:
        monkeypatch.setattr(solvers, "_result", altered_result(solvers._result))
    assert not one_run(name)["correct"]
