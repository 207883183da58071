"""The reading of the program's `repro.*` spans against the device's
programs (`chipbench/spans.py`): its pure functions on synthetic lists,
and `read` on a small trace recorded on one TPU v5e.

`data/criteo-1chip-spans.xplane.pb.gz` is the traced window of a run of
the criteo-1chip cell cut to n = 8,192 with a 0.5-s window (two solves
of three epochs), recorded on the chip from a program that opens
`repro.*` spans; `data/criteo-1chip.xplane.pb.gz` is one from a program
that opens none.
"""
import gzip
import os

import pytest

from chipbench import run, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "criteo-1chip-spans.xplane.pb.gz")
NO_SPANS = os.path.join(DATA, "criteo-1chip.xplane.pb.gz")
READERS = ("gap_span_share", "epoch_span_examples_per_s",
           "gap_programs_per_check", "host_reads_per_epoch")

# one solve: fit > epoch > (program, read), then fit > gap > read
SPANS = [(0, 100, "repro.fit"), (10, 40, "repro.epoch"),
         (12, 20, "repro.epoch.program"), (30, 40, "repro.read.rel_change"),
         (50, 80, "repro.gap"), (70, 80, "repro.read.gap"),
         (120, 130, "repro.state.load")]


def test_segments_name_time_by_the_innermost_span():
    assert spans.segments(SPANS) == [
        (0, 10, "repro.fit"), (10, 12, "repro.epoch"),
        (12, 20, "repro.epoch.program"), (20, 30, "repro.epoch"),
        (30, 40, "repro.read.rel_change"), (40, 50, "repro.fit"),
        (50, 70, "repro.gap"), (70, 80, "repro.read.gap"),
        (80, 100, "repro.fit"), (120, 130, "repro.state.load")]


def test_self_time_leaves_out_the_children():
    got = spans.self_seconds(spans.segments(SPANS))
    assert got == pytest.approx({
        "repro.fit": 40e-9, "repro.epoch": 12e-9,
        "repro.epoch.program": 8e-9, "repro.read.rel_change": 10e-9,
        "repro.gap": 20e-9, "repro.read.gap": 10e-9,
        "repro.state.load": 10e-9})
    assert spans.durations(SPANS, "repro.epoch") == pytest.approx([30e-9])


def test_a_span_opened_later_on_another_thread_is_innermost():
    # a prefetch thread's fetch overlaps the main thread's wait
    segs = spans.segments([(0, 50, "repro.epoch.program"),
                           (10, 30, "repro.ingest.wait"),
                           (20, 40, "repro.ingest.fetch")])
    assert segs == [(0, 10, "repro.epoch.program"),
                    (10, 20, "repro.ingest.wait"),
                    (20, 30, "repro.ingest.fetch"),
                    (30, 40, "repro.ingest.fetch"),
                    (40, 50, "repro.epoch.program")]


def test_programs_started_inside_a_span():
    programs = [(5, 9, "jit_a(1)"), (50, 55, "jit_b(2)"),
                (79, 90, "jit_c(3)"), (80, 85, "jit_d(4)")]
    assert spans.programs_in(SPANS, programs, "repro.gap") == [
        (50, 55, "jit_b(2)"), (79, 90, "jit_c(3)")]
    assert spans.programs_in(SPANS, programs, "repro.read.gap") == [
        (79, 90, "jit_c(3)")]
    assert spans.programs_in([], programs, "repro.gap") == []


def test_program_seconds_go_to_the_span_at_their_start():
    programs = [(14, 18, "jit__lambda(7)"), (14, 18, "jit__lambda(7)"),
                (72, 74, "jit_gather(9)"), (105, 110, "jit_x(1)")]
    got = spans.program_seconds(spans.segments(SPANS), programs)
    assert got == pytest.approx({
        ("repro.epoch.program", "jit__lambda(7)"): 8e-9,
        ("repro.read.gap", "jit_gather(9)"): 2e-9,
        (spans.NO_SPAN, "jit_x(1)"): 5e-9})


def test_idle_is_split_between_spans_and_none():
    busy = [[12, 30], [55, 75]]
    got = spans.idle_seconds(spans.segments(SPANS), busy, (0, 140))
    assert got == pytest.approx({
        "repro.fit": 40e-9, "repro.epoch": 2e-9,
        "repro.read.rel_change": 10e-9, "repro.gap": 5e-9,
        "repro.read.gap": 5e-9, "repro.state.load": 10e-9,
        spans.NO_SPAN: 30e-9})
    assert sum(got.values()) == pytest.approx(
        (140 - 18 - 20) * 1e-9)


# -- the recorded trace -----------------------------------------------------

def _raw(path):
    with gzip.open(path, "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def recorded():
    raw = _raw(FIXTURE)
    return spans.read.__wrapped__(raw), trace.reduce_trace(trace.load(raw))


def test_recorded_spans_tie_solves_epochs_and_gap_checks(recorded):
    tl, _ = recorded
    names = [s[2] for s in tl.spans]
    fits = names.count("repro.fit")
    assert fits >= 1 and len(tl.programs) == 1
    assert names.count("repro.epoch") == names.count("repro.gap") > fits
    assert names.count("repro.read.rel_change") == names.count("repro.epoch")
    assert names.count("repro.state.load") == fits


def test_recorded_gap_checks_launch_their_programs(recorded):
    tl, _ = recorded
    started = spans.programs_in(tl.spans, tl.programs[0], "repro.gap")
    checks = len(spans.durations(tl.spans, "repro.gap"))
    # 38 dispatched a check; the device clock sits up to 0.3 ms early
    # against the host's, so a check's first program can start before it
    assert 36 <= len(started) / checks <= 38
    assert all(p[2].startswith("jit_") for p in started)


def test_recorded_idle_agrees_with_trace_and_falls_under_spans(recorded):
    tl, reading = recorded
    assert tl.window_s == pytest.approx(reading.window_s)
    idle = spans.idle_seconds(spans.segments(tl.spans), tl.busy[0],
                              tl.window)
    total = sum(idle.values())
    assert total == pytest.approx(reading.window_s - reading.busy_s,
                                  rel=1e-9)
    assert idle.get(spans.NO_SPAN, 0.0) <= 0.1 * total


def _traced(tmp_path, monkeypatch, path):
    """`path`'s trace where `run.py` leaves the window's trace."""
    out = tmp_path / "plugins" / "profile" / "0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(_raw(path))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))


def test_readers_on_the_recorded_trace(tmp_path, monkeypatch, capsys):
    _traced(tmp_path, monkeypatch, FIXTURE)
    ctx = {"chips": 1, "n": 8192}
    got = {m: run._load_metric(m)(ctx) for m in READERS}
    assert 0 < got["gap_span_share"] < 100
    assert 80_000 < got["epoch_span_examples_per_s"] < 95_000
    assert 36 <= got["gap_programs_per_check"] <= 38
    assert got["host_reads_per_epoch"] == pytest.approx(14 / 3)
    out = capsys.readouterr().out
    assert "spans: idle under no repro span" in out
    assert ": jit__lambda(" in out          # the epoch program


def test_readers_are_silent_on_a_program_without_spans(tmp_path,
                                                       monkeypatch):
    _traced(tmp_path, monkeypatch, NO_SPANS)
    ctx = {"chips": 1, "n": 8192}
    assert all(run._load_metric(m)(ctx) is None for m in READERS)
