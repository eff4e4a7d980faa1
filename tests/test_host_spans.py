"""`benchmark/lib/host_spans.py` on synthetic (start, duration, name)
events: the program's spans told from the runtime's, the per-name table,
and the device's idle gaps split BY OVERLAP among the innermost span open
at each instant — plain arithmetic, checked where every number can be
worked out by hand (times in nanoseconds, as the trace gives them).
"""
import pytest

from benchmark.lib import host_spans
from benchmark.lib.host_spans import UNATTRIBUTED

MS = 1_000_000
SOLVE = "solve.lbfgs_streamed"
PASS = SOLVE + "/stream.pass"
FAMILIES = {"solve", "stream"}

# one pass of 100 ms inside a solve of 130: upload 10–50, dispatch 52–55,
# readback 55–60, upload 60–95; a host step 105–125
SPANS = [
    (0 * MS, 130 * MS, SOLVE),
    (5 * MS, 100 * MS, PASS),
    (10 * MS, 40 * MS, PASS + "/stream.upload"),
    (52 * MS, 3 * MS, PASS + "/stream.dispatch"),
    (55 * MS, 5 * MS, PASS + "/stream.readback"),
    (60 * MS, 35 * MS, PASS + "/stream.upload"),
    (105 * MS, 20 * MS, SOLVE + "/solve.host_step"),
]


def test_path_names_reduce_to_their_last_component():
    assert host_spans.last(PASS + "/stream.upload") == "stream.upload"
    assert host_spans.last("stream.pass") == "stream.pass"
    table = host_spans.summarize(SPANS)
    assert set(table) == {SOLVE, "stream.pass", "stream.upload",
                          "stream.dispatch", "stream.readback",
                          "solve.host_step"}
    up = table["stream.upload"]
    assert up["count"] == 2
    assert up["total_s"] == pytest.approx(0.075)
    assert (up["min_s"], up["median_s"], up["max_s"]) == pytest.approx(
        (0.035, 0.0375, 0.040))


def test_program_spans_are_told_by_family_and_window():
    host = SPANS + [
        (20 * MS, 5 * MS, "PjitFunction(_chunk_dz_phi_fn)"),  # the runtime
        (0, 140 * MS, "bench.section.unit"),            # the benchmark's
        (1 * MS, 120 * MS, "bench.solve"),
        (30 * MS, MS, "stream"),                        # no dotted name
        (200 * MS, 10 * MS, PASS + "/stream.upload"),   # outside the unit
    ]
    got = host_spans.program_spans(host, [(0, 140 * MS)], FAMILIES)
    assert sorted(got) == sorted(SPANS)
    assert host_spans.program_spans(host, [(0, 140 * MS)], {"game"}) == []


def test_a_gap_across_three_spans_is_split_by_overlap():
    # one idle gap 45–70 ms: 5 of the first upload, 2 of the pass itself,
    # 3 of the dispatch, 5 of the readback, 10 of the second upload — its
    # midpoint (57.5) would have given all 25 to the readback
    split = host_spans.idle_by_span([(45 * MS, 70 * MS)], SPANS)
    assert split == pytest.approx({
        "stream.upload": 0.015, "stream.pass": 0.002,
        "stream.dispatch": 0.003, "stream.readback": 0.005})
    assert sum(split.values()) == pytest.approx(0.025)


def test_innermost_span_wins_and_the_rest_is_unattributed():
    idle = [(2 * MS, 8 * MS),       # 3 of the solve, 3 of the pass
            (96 * MS, 110 * MS),    # 9 pass, 0 solve (100–105 is 5), 5 step
            (120 * MS, 140 * MS)]   # 5 step, 5 solve, 10 under no span
    split = host_spans.idle_by_span(idle, SPANS)
    assert split == pytest.approx({
        SOLVE: 0.003 + 0.0 + 0.005, "stream.pass": 0.003 + 0.009,
        "solve.host_step": 0.005 + 0.005, UNATTRIBUTED: 0.010})
    # the parts add up to the idle total
    assert sum(split.values()) == pytest.approx(
        sum(hi - lo for lo, hi in idle) / 1e9)
    assert host_spans.idle_by_span(idle, []) == pytest.approx(
        {UNATTRIBUTED: 0.040})
    assert host_spans.idle_by_span([], SPANS) == {}


def test_frames_are_the_spans_that_enclose_others():
    assert host_spans.frames(SPANS) == {SOLVE, "stream.pass"}
    # the parent's program: a pass encloses nothing there
    assert host_spans.frames(SPANS[:2]) == {SOLVE}


@pytest.mark.parametrize("device", ["busy", "none"])
def test_reduce_host_over_the_units(device):
    """Device busy 10–45 and 70–96 ms in a unit of 0–140: idle 0–10,
    45–70, 96–140 = 79 ms, of which the solve and the pass themselves and
    the 10 ms under no span are what no leaf span covers."""
    trace = {"host": SPANS + [(0, 140 * MS, "bench.section.unit")],
             "devices": [[(10 * MS, 35 * MS, "fusion.1"),
                          (70 * MS, 26 * MS, "fusion.2")]]
             if device == "busy" else []}
    table = host_spans.reduce_host(trace, FAMILIES)
    assert table["spans"]["stream.upload"]["count"] == 2
    if device == "none":
        assert set(table) == {"spans"}
        return
    assert table["idle_s"] == pytest.approx(0.079)
    assert sum(table["idle_by_span"].values()) == pytest.approx(0.079)
    assert table["frames"] == [SOLVE, "stream.pass"]
    split = table["idle_by_span"]
    assert split["stream.upload"] == pytest.approx(0.005 + 0.010)
    assert split["solve.host_step"] == pytest.approx(0.020)
    assert split[UNATTRIBUTED] == pytest.approx(0.010)
    # 0–5, 125–130 under the solve alone; 5–10, 50–52, 96–105 under the pass
    assert split[SOLVE] == pytest.approx(0.005 + 0.005)
    assert split["stream.pass"] == pytest.approx(0.005 + 0.002 + 0.009)
    assert table["uncovered_s"] == pytest.approx(0.010 + 0.010 + 0.016)
