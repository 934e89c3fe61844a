"""``host_trace`` and the readers over it: on a hand-made record where the
answers are known, and on ``host_trace_sample.json``, a few decode steps cut
from a real traced run of ``chat-decode`` on a TPU v5e."""

import glob
import json
import os

import pytest

from perfbench import harness, host_trace
from perfbench.readers import (event_attr_percentile, host_gap,
                               span_self_time)

HERE = os.path.dirname(os.path.abspath(__file__))


def _hand():
    """Two device programs with a gap of 100 ns between them (100..200),
    and a shorter one of 20 (250..270)."""
    ops = [["fusion.1 f32[1]", 0.0, 100.0], ["fusion.2 f32[1]", 200.0, 50.0],
           ["fusion.3 f32[1]", 270.0, 30.0]]
    step = [["serving.decode", 0.0, 130.0],
            ["serving.decode.wait", 10.0, 100.0],      # ends 110
            ["serving.decode.emit", 110.0, 15.0],      # ends 125
            ["serving.publish", 132.0, 8.0],           # ends 140
            ["serving.admit", 140.0, 20.0],            # ends 160
            ["serving.decode", 165.0, 140.0],          # the next step
            ["serving.decode.build", 165.0, 10.0],     # ends 175
            ["serving.decode.launch", 175.0, 40.0],    # ends 215
            ["jit.call", 176.0, 38.0],
            ["jit.dispatch", 180.0, 30.0]]             # ends 210
    door = [["serving.submit", 90.0, 120.0]]           # another thread
    return {"trace": {"planes": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": []}], "window_s": 1e-6},
            "host": {"threads": [{"name": "http", "spans": door},
                                 {"name": "step", "spans": step}],
                     # the clock: each program starts as its enqueue ends
                     "programs": [[7, 0.0], [8, 200.0], [9, 270.0]],
                     "enqueues": [[8, 200.0], [9, 268.0], [None, 999.0]]}}


def test_innermost_span_wins_and_the_parts_sum_to_the_gaps():
    rec = _hand()
    split = host_trace.split_of(rec)
    assert split["steps"] == 2
    by = split["by_span"]
    assert by == {
        "serving.decode.wait": 10.0,     # 100..110
        "serving.decode.emit": 15.0,     # 110..125
        "serving.decode": 5.0 + 20.0,    # under no phase: 125..130, 250..270
        None: 2.0 + 5.0,                 # under no span: 130..132, 160..165
        "serving.publish": 8.0, "serving.admit": 20.0,
        "serving.decode.build": 10.0,    # 165..175
        "serving.decode.launch": 1.0,    # 175..176
        "jit.call": 4.0,                 # 176..180
        "jit.dispatch": 20.0}            # 180..200: the device starts
    gaps = host_trace.device_gaps(rec["trace"]["planes"][0]["ops"])
    assert gaps == [(100.0, 200.0), (250.0, 270.0)]
    assert sum(by.values()) == pytest.approx(sum(b - a for a, b in gaps))


def test_other_threads_are_ignored():
    rec = _hand()
    assert host_trace.step_thread(rec["host"])["name"] == "step"
    assert "serving.submit" not in host_trace.split_of(rec)["by_span"]
    only_door = dict(rec, host={"threads": rec["host"]["threads"][:1]})
    assert host_trace.split_of(only_door) is None
    assert host_gap.read(only_door, spans=["serving.idle"]) is None


def test_device_clock_is_set_on_the_hosts_by_the_latest_enqueue():
    rec = _hand()
    assert host_trace.device_offset(rec["host"]) == 0.0
    # the device planes 30 ns early: program 8 "starts" at 170, before the
    # enqueue that ends at 200 — the repair moves every gap by 30
    early = dict(rec, trace={"planes": [{"ops": [
        [o[0], o[1] - 30.0, o[2]]
        for o in rec["trace"]["planes"][0]["ops"]]}]})
    early["host"] = dict(rec["host"], programs=[
        [r, t - 30.0] for r, t in rec["host"]["programs"]])
    assert host_trace.device_offset(early["host"]) == 30.0
    assert host_trace.split_of(early) == host_trace.split_of(rec)
    unrepaired = host_trace.split(early["trace"]["planes"][0]["ops"],
                                  rec["host"]["threads"][1]["spans"])
    assert unrepaired["by_span"]["serving.decode.wait"] == 40.0   # not 10
    assert "jit.dispatch" not in unrepaired["by_span"]           # not 20
    # no enqueue that names a program of the slice: nothing is reported
    blind = dict(rec, host=dict(rec["host"], enqueues=[[99, 5.0]]))
    assert host_trace.device_offset(blind["host"]) is None
    assert host_trace.split_of(blind) is None
    assert host_gap.read(blind, spans=["serving.decode.wait"]) is None


def _metric_files():
    out = {}
    for path in glob.glob(os.path.join(harness.HERE, "layer_metrics",
                                       "host_gap_*.json")):
        with open(path) as f:
            spec = json.load(f)
        out[spec["name"]] = spec["args"]
    return out


def test_host_gap_metrics_sum_to_the_gap_total_per_step():
    rec = _hand()
    files = _metric_files()
    assert len(files) == 8
    reads = {n: host_gap.read(rec, **a) for n, a in files.items()}
    assert reads["host_gap_readback_ms"] == pytest.approx(10e-6 / 2)
    assert reads["host_gap_launch_ms"] == pytest.approx(25e-6 / 2)
    assert reads["host_gap_emit_ms"] == pytest.approx(23e-6 / 2)
    assert reads["host_gap_no_work_ms"] == 0.0
    assert reads["host_gap_release_ms"] == 0.0
    # serving.decode's own 25 ns are nobody's: unnamed, with the 7 ns
    # under no span at all
    assert reads["host_gap_unnamed_ms"] == pytest.approx(32e-6 / 2)
    assert sum(reads.values()) == pytest.approx(120e-6 / 2)


def test_sample_from_the_chip():
    with open(os.path.join(HERE, "host_trace_sample.json")) as f:
        rec = json.load(f)
    split = host_trace.split_of(rec)
    gaps = host_trace.device_gaps(rec["trace"]["planes"][0]["ops"])
    total = sum(b - a for a, b in gaps)
    assert split["steps"] >= 3
    assert sum(split["by_span"].values()) == pytest.approx(total)
    reads = {n: host_gap.read(rec, **a) for n, a in _metric_files().items()}
    assert sum(reads.values()) == pytest.approx(
        total * 1e-6 / split["steps"])
    assert all(v >= 0 for v in reads.values())
    # the phases leave no hole: what no metric claims is under a tenth
    assert reads["host_gap_unnamed_ms"] < 0.1 * sum(reads.values())
    # the device starts inside the compiled call, and the host is still
    # in its read-back when the program ends
    assert reads["host_gap_launch_ms"] > 0
    assert reads["host_gap_readback_ms"] > 0


def test_find_refuses_a_file_from_another_run(tmp_path, monkeypatch):
    def xplane(cell, stamp, mtime):
        d = tmp_path / f"{cell}.trace" / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        p = d / "host.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (mtime, mtime))
        return str(p)

    mine = xplane("chat-decode", "2026_01_01", 1000)
    other = xplane("train-4k", "2026_01_02", 2000)       # newer
    first = {mine: [500.0, 7.0], other: [900.0, 3.0]}
    monkeypatch.setattr(host_trace, "_first_device_op", first.get)
    rec = {"trace": {"planes": [{"ops": [["x f32[1]", 500.0, 7.0]]}]}}
    assert host_trace.find(rec, str(tmp_path)) == mine
    rec["trace"]["planes"][0]["ops"][0] = ["x f32[1]", 501.0, 7.0]
    assert host_trace.find(rec, str(tmp_path)) is None
    assert host_trace.find({"trace": None}, str(tmp_path)) is None
    assert host_trace.find({"trace": {"planes": []}}, str(tmp_path)) is None


def _ev(kind, name, ts, sid=0, parent=0, **attrs):
    return {"kind": kind, "name": name, "ts": ts, "span": sid,
            "parent": parent, "attrs": attrs}


SPANS = {"window": [10.0, 20.0], "spans": [
    _ev("B", "jit.call", 11.000, 1), _ev("B", "jit.dispatch", 11.001, 2, 1),
    _ev("E", "jit.dispatch", 11.004, 2), _ev("E", "jit.call", 11.006, 1),
    # two children that overlap: covered once; one sticking out: clipped
    _ev("B", "jit.call", 12.000, 3), _ev("B", "jit.dispatch", 12.002, 4, 3),
    _ev("B", "jit.inner", 12.003, 5, 3), _ev("E", "jit.dispatch", 12.005, 4),
    _ev("E", "jit.inner", 12.012, 5), _ev("E", "jit.call", 12.010, 3),
    _ev("B", "jit.call", 9.0, 6), _ev("E", "jit.call", 9.5, 6),   # before
    _ev("B", "jit.call", 19.0, 7),                                # open
    _ev("i", "serving.http.token", 11.0, lag_ms=1.0, rid=1, index=0),
    _ev("i", "serving.http.token", 12.0, lag_ms=3.0, rid=1, index=1),
    _ev("i", "serving.http.token", 13.0, lag_ms=2.0, rid=2, index=0),
    _ev("i", "serving.http.token", 30.0, lag_ms=99.0, rid=2, index=1),
    _ev("i", "serving.complete", 13.0, rid=2)]}


def test_span_self_time_is_duration_less_what_children_cover():
    # 6 - 3 ms; 10 - (8 covered: 12.002..12.010) = 2 ms
    assert span_self_time.read(SPANS, span="jit.call", q=0) == \
        pytest.approx(2.0)
    assert span_self_time.read(SPANS, span="jit.call", q=100) == \
        pytest.approx(3.0)
    assert span_self_time.read(SPANS, span="jit.dispatch", q=50) == \
        pytest.approx(3.0)
    assert span_self_time.read(SPANS, span="train.captured_step") is None


def test_event_attr_percentile_reads_the_window_only():
    read = event_attr_percentile.read
    assert read(SPANS, event="serving.http.token", attr="lag_ms", q=50) == 2.0
    assert read(SPANS, event="serving.http.token", attr="lag_ms",
                q=100) == 3.0
    assert read(SPANS, event="serving.http.token", attr="nope", q=50) is None
    assert read(SPANS, event="serving.nothing", attr="lag_ms", q=50) is None
