"""Required work depends on the shapes alone, not on the route."""
import pytest

from chipbench import run, work


def _shape(name):
    cell = run.load_cell(name)
    eng = cell["cell"]["engine"]
    return (cell["config"], cell["config"]["n"], eng.get("chunks", 1),
            eng.get("lanes", 1))


def test_v_goes_in_and_out_once_a_call_on_every_lane():
    cfg, n, _, _ = _shape("criteo-1chip")
    one = work.epoch_work(cfg, n, chunks=1, lanes=1)
    four = work.epoch_work(cfg, n, chunks=2, lanes=4)
    assert four.flops == one.flops
    assert four.bytes - one.bytes == 7 * 2 * 4 * cfg["d"]


def test_criteo_epoch_counts_the_real_nonzeros():
    cfg, n, chunks, lanes = _shape("criteo-1chip")
    w = work.epoch_work(cfg, n, chunks=chunks, lanes=lanes)
    assert work.row_width(cfg) == 39 and cfg["nnz_multiple"] == 8
    assert w.flops == 4 * 39 * n
    assert w.bytes == n * (8 * 39 + 12) + 2 * 4 * cfg["d"]
    assert work.gap_work(cfg, n).bytes == n * (8 * 39 + 8) + 4 * cfg["d"]


def test_dense_row_bytes():
    cfg, n, chunks, lanes = _shape("higgs-1chip")
    assert work.row_bytes(cfg) == 4 * 28
    assert work.gap_work(cfg, n).flops == 2 * 28 * n


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peak("no such chip")
    assert work.peak("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}


def test_roofline_names_its_bound():
    pk = {"flops": 1e12, "bytes": 1e9}
    assert work.roofline_seconds(work.Work(1e12, 1e6), pk) == (1.0, "flops")
    assert work.roofline_seconds(work.Work(1e6, 2e9), pk) == (2.0, "bytes")
