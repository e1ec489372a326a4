"""The comparison fails the control and the faults of a served search.

The control is the reference in the program's place, computed as a TPU
computes float32 at ``high`` precision (three bf16 passes); the faults
break the timed path underneath a whole run of a tiny cell: half of each
micro-batch left out (its rows answered with the other half's answers),
and one answer altered where it is produced.  Each must make ``correct``
false.  The same control is read on the chip at the cells' own size by
``chipbench/readings.py``.
"""

import numpy as np
import pytest

from chipbench import check, data, reference
from conftest import TINY_CORPUS, tiny_configs


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("k", [5, 20])
def test_control_fails_and_reference_passes(seed, k):
    cfg = tiny_configs()["tiny-bruteforce"]
    X_dev, Q_dev = data.make(TINY_CORPUS, seed)
    X, Q = np.asarray(X_dev), np.asarray(Q_dev)
    qidx = np.arange(len(Q))
    groups = ["default"]

    def judge(d, i):
        answers = {"qidx": qidx, "ids": i, "dists": d,
                   "group": np.zeros(len(qidx), np.int64), "groups": groups}
        return check.judge(X, X_dev, Q, answers, k, cfg["guarantees"],
                           cfg["check"], unanswered=0)

    d, i = reference.scan(X_dev, Q, k)
    sound = judge(d, i)
    assert sound["correct"], sound["checks"]
    d, i = reference.control(X_dev, Q, qidx, k)
    ctrl = judge(d, i)
    assert not ctrl["correct"]
    assert ctrl["checks"]["dist_err"][0] > 3 * sound["checks"]["dist_err"][0]


def _half_left_out(real):
    def run_padded(self, Qb, n_live, overrides):
        dists, ids, cov = real(self, Qb, n_live, overrides)
        dists, ids = np.array(dists), np.array(ids)
        half = max(1, n_live // 2)
        for r in range(half, n_live):
            dists[r], ids[r] = dists[r - half], ids[r - half]
        return dists, ids, cov
    return run_padded


def _answer_altered(real):
    def run_padded(self, Qb, n_live, overrides):
        dists, ids, cov = real(self, Qb, n_live, overrides)
        ids = np.array(ids)
        ids[0, 0] = (ids[0, 0] + 1) % self.state.stat("n")
        return dists, ids, cov
    return run_padded


@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered])
@pytest.mark.parametrize("cell", ["tiny-bf-bulk", "tiny-ivf-open"])
def test_faults_make_correct_false(tiny_root, run_cell, monkeypatch, cell,
                                   fault):
    from repro.serve import Engine

    rc, line = run_cell(tiny_root, cell, seconds=1.0)
    assert rc == 0 and line["correct"] is True
    monkeypatch.setattr(Engine, "_run_padded",
                        fault(Engine._run_padded))
    rc, line = run_cell(tiny_root, cell, seconds=1.0)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_no_accelerator_prints_no_result(tiny_root, capsys):
    from chipbench import run

    rc = run.main(["--workload", "tiny-bf-bulk", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=tiny_root)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_without_the_program_prints_no_result(tiny_root, capsys):
    from conftest import cpu_devices

    from chipbench import run

    (tiny_root / "src").unlink()
    rc = run.main(["--workload", "tiny-bf-bulk", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=tiny_root,
                  accelerator=cpu_devices)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
