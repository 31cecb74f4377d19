"""The control: the reference put in the program's place and computed in
float8, the precision below the configuration's bfloat16, fails the
comparison's limits; so do the planted faults. At the tiny test size on the
CPU; the same readings at the cells' own sizes on the chip come from
``bench/control.py``."""
import json
import os

import pytest

import bench_tiny

from bench import control


def _cfg(name):
    with open(os.path.join(os.path.dirname(__file__), "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", bench_tiny.config_names())
def test_control_and_faults_fail_a_limit(name):
    cfg = _cfg(name)
    readings = control.readings(cfg, bench_tiny.SEED,
                                modes=("fp8", "half_batch", "frozen"))
    lim = cfg["limits"]
    for mode, got in readings.items():
        assert any(got[k] > lim[k] for k in lim if k in got), (mode, got)
    assert readings["frozen"]["change1_leaf_gap"] == 1.0
