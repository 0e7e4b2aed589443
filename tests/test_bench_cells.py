"""The benchmark's DeterDupl and HBM-sized one-chip cells, at cut sizes.

``v5e4.deterdupl.lg20`` sorts the paper's DeterDupl instance (log2 p
distinct keys: 0 and 1 at p = 4) on four chips, where RAMS' tie-break tags
decide every splitter comparison.  ``v5e1.uniform.lg26`` is the one-chip
sort at 2^26 keys, the size its configuration stands for: at a cut size
its run here is the p = 1 path under that configuration's ``sort_config``.
Both run through the benchmark's own files (``bench/run.load_cell``,
``bench/gen``) on the CPU, at sizes cut far below the cells'; nothing at
2^26 runs in the suite.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.api import SortConfig, default_mesh, psort

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import gen, run  # noqa: E402

CELLS = {"v5e4.deterdupl.lg20": ("v5e4-u32", 4, "deter_dupl"),
         "v5e1.uniform.lg26": ("v5e1-u32-hbm", 1, "uniform")}
SEED = 2**31 + 19
# RAMS at p = 4 is one level with b = 2 bits, k = 4 groups.  The ε-balance
# argument of core/rams.py: a group, here one PE, receives its share n / p
# plus at most one bucket, and a bucket holds about total / (b·k) keys, so
# a PE ends with at most (1 + 1/b)·n/p = 1.5·n/p.  The tie-broken sort read
# 1.06 to 1.21 over 60 seeds at 2^16; without tags half the keys are one
# value, which no splitter can divide, and one PE is sent 2·n/p.
BALANCE_BOUND = 1.5


@pytest.fixture(scope="module")
def deter_dupl():
    cell = run.load_cell("v5e4.deterdupl.lg20", ROOT)
    keys, = gen.inputs(dict(cell.traffic, n=1 << 16, distinct_inputs=1),
                       p=cell.chips, seed=SEED, dtype=cell.dtype, root=ROOT)
    assert set(np.unique(keys).tolist()) == {0, 1}
    return keys


def _rams(keys, algorithm):
    out, info = psort(keys, config=SortConfig(mesh=default_mesh(4),
                                              algorithm=algorithm),
                      return_info=True)
    assert info["algorithm"] == algorithm
    return np.asarray(out), info


def test_rams_sorts_deter_dupl_exactly_within_the_balance_bound(deter_dupl):
    out, info = _rams(deter_dupl, "rams")
    assert np.array_equal(out, np.sort(deter_dupl))
    assert info["overflow"] == 0
    assert info["balance"] < BALANCE_BOUND


def test_without_tie_break_deter_dupl_breaks_the_bound(deter_dupl):
    # the paper's negative result: the bound above can fail
    _, info = _rams(deter_dupl, "ntb-ams")
    assert info["overflow"] > 0 or info["balance"] >= BALANCE_BOUND


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_run_of_the_new_cell_at_a_cut_size_is_correct(name):
    config, chips, instance = CELLS[name]
    cell = run.load_cell(name, ROOT)
    assert (cell.config["name"], cell.chips, cell.traffic["instance"]) == \
        (config, chips, instance)
    assert cell.dtype == np.uint32
    n = 1 << 12
    if instance == "deter_dupl":
        # auto picks RAMS only from n = 2^20, as the cell does: pin it so
        # the cut size still breaks its ties with the tags
        cell.config = dict(cell.config, sort_config=dict(
            cell.config["sort_config"], algorithm="rams"))
        n = 1 << 14
    cell.traffic = dict(cell.traffic, n=n)
    result, checks = run.run_cell(cell, SEED, 0.3, False,
                                  jax.devices()[:cell.chips],
                                  {"hbm_bytes_per_s": 819e9})
    assert result["correct"] and result["failed"] == 0, checks
    assert checks["calls_checked"][0] == result["attempted"] >= run.MIN_CALLS
