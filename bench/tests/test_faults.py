"""A run with the timed path broken underneath comes out not correct: each
fault a cell can have, planted where the program produces its result
(``bench/faults.py``), at smoke sizes on the CPU.  The chip's search for a
TPU is skipped; the rest of a run is driven as it is on the chip.  The
limits are for the smoke sizes, where sound runs read below a tenth of
them."""
import time

import pytest

from bench import faults, serve, train
from bench.tests import smoke

SERVE_LIMIT = 0.05
TRAIN_LIMITS = {"loss_gap": 0.03, "grad_norm_gap": 0.03,
                "update_norm_gap": 0.03}


def run(driver, cell, fault=None, seed=3):
    return driver.run(cell, seed=seed, seconds=2.0, trace=False,
                      t_start=time.perf_counter(), fault=fault)


@pytest.mark.parametrize("fault", [None, faults.altered_token,
                                   faults.stale_segment_state])
def test_serving_faults_are_caught(fault):
    rec = run(serve, smoke.backlog_cell(SERVE_LIMIT), fault)
    if fault is None:
        assert rec["correct"], rec["checks"]
    else:
        assert not rec["correct"]
        assert rec["checks"]["max_logit_gap"]["value"] > SERVE_LIMIT


@pytest.mark.parametrize("fault,number", [
    (None, None),
    (faults.unchanged_state, "update_norm_gap"),
    (faults.half_batch, "grad_norm_gap"),
])
def test_training_faults_are_caught(fault, number):
    rec = run(train, smoke.train_cell(dict(TRAIN_LIMITS)), fault)
    if fault is None:
        assert rec["correct"], rec["checks"]
    else:
        assert not rec["correct"]
        assert rec["checks"][number]["value"] > TRAIN_LIMITS[number]
