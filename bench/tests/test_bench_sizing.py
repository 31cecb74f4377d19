"""Steps per run from --seconds and a configuration's sizing numbers."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402

SIZING = {"step_s": 2.0, "save_s": 25.0}


def _traffic(name):
    return harness.load_traffic(name)


def test_steady_steps_fill_the_window_after_the_save():
    # set-up's 2 steps, then (51 - 25) / 2 = 13 in the window
    assert harness.plan_steps(51, SIZING, _traffic("steady")) == 15
    assert harness.plan_steps(45, SIZING, _traffic("steady")) == 12


def test_short_windows_keep_steps_in_the_window():
    assert harness.plan_steps(1, SIZING, _traffic("steady")) == 4
    assert harness.plan_steps(26, SIZING, _traffic("steady")) == 4


def test_committed_sizing_gives_each_cell_its_steps():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        cfg = harness.load_config(spec, cell["config"])
        traffic = harness.load_traffic(cell["traffic"])
        n = harness.plan_steps(spec["run_seconds"], cfg["sizing"], traffic)
        assert n >= harness.REFERENCE_STEPS + traffic["min_window_steps"]
