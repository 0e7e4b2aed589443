"""The 64-bit four-chip cell and the small one-chip cell: whole runs at a
small size on the CPU, and ``classify_roofline`` on synthetic traces."""
import numpy as np
import pytest

from bench import run
from bench import trace as tr


def _view(devices, chips=2):
    host = [(tr.WINDOW, 0, 100), (tr.CALL, 5, 40), (tr.CALL, 45, 90)]
    return tr.View(tr.Trace((0, 100), host, devices), n=1000, chips=chips,
                   peaks={"hbm_bytes_per_s": 1e12})


def test_reads_the_named_kernel_on_the_chip_where_it_takes_longest():
    label = "partition_planes.3 custom-call (s32[64,128], s32[64,128])"
    view = _view({
        "TPU:0": [(label, 10, 14), ("fusion.1", 14, 30), (label, 50, 54)],
        "TPU:1": [(label, 10, 20), ("partition_tile.2 custom-call", 50, 60),
                  ("all-to-all.3", 60, 70)]})
    # TPU:1's kernel ops: 10 ns over two calls; least = 12 B * 500 / 1e12
    least = (8 + 4) * 1000 / 2 / 1e12
    assert run.reader("classify_roofline")(view) == \
        pytest.approx(100 * least / 5e-9)


def test_nothing_to_read_without_the_kernel():
    view = _view({"TPU:0": [("fusion.1", 10, 30),
                            ("partition_tile.1 custom-call", 30, 40)]},
                 chips=1)
    assert run.reader("classify_roofline")(view) is None
    assert run.reader("classify_roofline")(_view({}, chips=1)) is None


def test_ops_outside_the_calls_do_not_count():
    label = "partition_planes.1 custom-call (s32[8,128])"
    view = _view({"TPU:0": [(label, 0, 5), (label, 40, 45),
                            (label, 20, 22)]}, chips=1)
    least = (8 + 4) * 1000 / 1e12
    assert run.reader("classify_roofline")(view) == \
        pytest.approx(100 * least / 1e-9)


@pytest.mark.parametrize("name,chips,dtype", [
    ("v5e4u64.uniform.lg20", 4, np.uint64), ("v5e1.uniform.lg16", 1,
                                             np.uint32)])
def test_a_run_of_a_new_cell_at_a_small_size_is_correct(name, chips, dtype):
    import jax
    cell = run.load_cell(name)
    assert cell.dtype == dtype
    assert [m["name"] for m in cell.end_to_end] == \
        ["keys_per_s", "peak_bytes_per_key", "setup_s"]
    cell.traffic = dict(cell.traffic, n=1 << 12)
    result, checks = run.run_cell(cell, 2**31 + 17, 0.3, False,
                                  jax.devices()[:chips],
                                  {"hbm_bytes_per_s": 819e9})
    assert result["correct"] and result["failed"] == 0, checks
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
