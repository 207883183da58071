"""The criteo-dp4 cell's readers (`collective_exposed_share`,
`mesh_epochs_to_gap`, `chip_examples_per_s`) on a synthetic trace of
four chips and a synthetic span list, and on the one-chip recording,
which holds no collective."""
import gzip
import importlib.util
import os
from types import SimpleNamespace

import pytest

from chipbench import run, spans

DATA = os.path.join(os.path.dirname(__file__), "data")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader_module(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(start, end, name):
    return SimpleNamespace(start_ns=start, duration_ns=end - start, name=name)


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


# HLO text as the device planes hold it: the epoch's collectives are
# named after the jax primitive, the certificate's after the opcode
A2A = "%all_to_all.47 = f32[4,512,1]{1,2,0} all-to-all(%x), dimensions={0}"
PSUM = "%psum.12 = f32[1000000]{0} all-reduce(%fusion.16), to_apply=%r"
KERNEL = ('%vmap_jit_sdca_sparse_bucket_kernel__.1 = f32[8] custom-call(%a),'
          ' custom_call_target="tpu_custom_call"')


def test_collective_exposed_share_reads_the_opcode_on_every_chip():
    reader = _reader_module("collective_exposed_share")
    host = _plane("/host:CPU", Python=[_ev(0, 1000, "bench.window")])
    chips = [
        # chip 0: an all-to-all of 100 ns alone, a psum of 50 ns half
        # under the kernel, a copy that is not a collective
        _plane("/device:TPU:0", XLA_Ops=[
            _ev(100, 200, A2A), _ev(300, 350, PSUM), _ev(325, 600, KERNEL),
            _ev(700, 800, "%copy.3 = f32[8]{0} copy(%all_to_all.47)")]),
        # chip 1: one psum of 40 ns, alone; its async twin on the side
        _plane("/device:TPU:1", XLA_Ops=[_ev(900, 940, PSUM)],
               Async_XLA_Ops=[_ev(900, 940, PSUM)]),
        # a chip the cell does not use
        _plane("/device:TPU:4", XLA_Ops=[_ev(0, 1000, A2A)])]
    got = reader.exposed_share(SimpleNamespace(planes=[host] + chips), 4)
    assert got == pytest.approx(100.0 * ((100 + 25) / 1000 + 40 / 1000) / 2)


def test_collective_exposed_share_reads_zero_on_one_chip(tmp_path,
                                                        monkeypatch):
    out = tmp_path / "plugins" / "profile" / "0"
    out.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "criteo-1chip.xplane.pb.gz")) as f:
        (out / "host.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    got = run._load_metric("collective_exposed_share")({"chips": 1})
    assert got == 0.0


def test_mesh_epochs_to_gap_averages_the_solves():
    ctx = {"solves": [{"epochs": 3}, {"epochs": 4}, {"epochs": 3},
                      {"epochs": 3}]}
    assert run._load_metric("mesh_epochs_to_gap")(ctx) == 3.25


def _timeline(span_list):
    return spans.Timeline(window=(0, 10_000_000_000), spans=span_list,
                          programs=[[]] * 4, busy=[[]] * 4)


def test_chip_examples_per_s_divides_the_epochs_over_the_chips(
        monkeypatch):
    # two epochs of 2.5 s and 3.5 s over n = 1,048,576 rows on 4 chips
    tl = _timeline([(0, 2_500_000_000, "repro.epoch"),
                    (2_600_000_000, 2_700_000_000, "repro.gap"),
                    (3_000_000_000, 6_500_000_000, "repro.epoch")])
    monkeypatch.setattr(spans, "window", lambda ctx: tl)
    got = run._load_metric("chip_examples_per_s")(
        {"n": 1_048_576, "chips": 4})
    assert got == pytest.approx(262_144 * 2 / 6.0)


def test_chip_examples_per_s_is_silent_without_epoch_spans(monkeypatch):
    monkeypatch.setattr(spans, "window",
                        lambda ctx: _timeline([(0, 10, "bench.solve")]))
    assert run._load_metric("chip_examples_per_s")(
        {"n": 1_048_576, "chips": 4}) is None
