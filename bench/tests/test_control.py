"""The precision control at the smoke sizes: the float32 reference computed
with float8 products in the program's place reads at least three times
what the program reads on one of each cell's numbers (on the chip, at the
cells' own sizes, ``bench/control.py`` gives the readings the limits are
set from)."""
from bench import control
from bench.tests import smoke


def test_serving_control_separates():
    rows = control.serve_readings(smoke.backlog_cell(), [5, 6], {5, 6}, 2.0)
    for r in rows:
        assert r["control"]["max_logit_gap"] > \
            3 * r["program"]["max_logit_gap"]


def test_training_control_and_half_batch_separate():
    rows = control.train_readings(smoke.train_cell(), [5], {5})
    r = rows[0]
    for reading in ("control", "half_batch"):
        assert any(r[reading][k] > 3 * r["program"][k] for k in r["program"])
