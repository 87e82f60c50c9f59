"""bench/tests/test_run.py runs its tests on every cell of BENCHMARK.json.
One of them, the bfloat16 control, reaches into bench/jobs/train_window.py
and judges with bench/reference_gbdt.py: it holds for the cells that job
runs. A cell with a job and a reference of its own keeps its control
beside them (test_clicklog.py), and is left out of that one test here."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def job_of(cell):
    with open(os.path.join(os.path.dirname(HERE), "workloads",
                           cell + ".json")) as f:
        return json.load(f)["job"]


def pytest_collection_modifyitems(items):
    for item in items:
        if item.path.name != "test_run.py" or \
                item.originalname != "test_the_bfloat16_control_is_not_correct":
            continue
        cell = item.callspec.params["cell"]
        if job_of(cell) != "train_window":
            item.add_marker(pytest.mark.skip(
                reason="%s runs bench/jobs/%s.py, whose control is in "
                       "test_clicklog.py" % (cell, job_of(cell))))
