#!/usr/bin/env python3
"""The yardstick's own arithmetic, checked on events and a layout made by
hand: `python3 benchmark/lib/selfcheck.py` (CPU, seconds, no jax device
work). Exit code 0 means every check held.
"""
from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.lib.xpass_bytes import xpass_evaluation_bytes  # noqa: E402

S = 1_000_000_000  # one second, in the trace's nanoseconds


def close(a, b, what):
    if abs(a - b) > 1e-9 * max(1.0, abs(b)):
        raise AssertionError(f"{what}: got {a}, want {b}")


def check_intervals():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (7, 7), (6, 6.5)])
    assert merged == [(0, 4), (5, 7)], merged  # touching pieces join
    close(tr.overlap(merged, 1, 6), 3 + 1, "overlap")
    close(tr.overlap(merged, 10, 20), 0, "overlap outside")
    assert tr.gaps(merged, -1, 9) == [(-1, 0), (4, 5), (7, 9)]
    assert tr.gaps(merged, 1, 3) == []
    assert tr.gaps([], 2, 5) == [(2, 5)]


def check_self_times():
    # a `while` of 10 s holding two bodies of 3 s, one of which holds a
    # 1 s child; then a lone 2 s op of the same name as a body
    events = [(0, 10 * S, "while"), (1 * S, 3 * S, "body"),
              (2 * S, 1 * S, "child"), (5 * S, 3 * S, "body"),
              (12 * S, 2 * S, "body")]
    got = tr.self_times(events)
    close(got["while"], 10 - 3 - 3, "while self time")
    close(got["body"], (3 - 1) + 3 + 2, "body self time")
    close(got["child"], 1, "child self time")
    close(sum(got.values()), 12, "self times sum to the union")


def check_reduce():
    # one device: busy 1-3 s and 4-5 s inside a unit section of 0-6 s; a
    # micro section 7-8 s fully busy; host annotations name the gaps
    ops = [(1 * S, 2 * S, "fusion.1"), (1 * S, 1 * S, "inner"),
           (4 * S, 1 * S, "fusion.2"), (7 * S, 1 * S, "fusion.1")]
    host = [(0, 6 * S, tr.SECTION_PREFIX + "unit"),
            (7 * S, 1 * S, tr.SECTION_PREFIX + "xpass"),
            (0, 3.2 * S, "train.read"), (3.2 * S, 2.8 * S, "train.train"),
            (3.3 * S, 0.4 * S, "train.train/inner")]
    out = tr.reduce_trace(
        {"devices": [ops], "host": host, "inventory": {}},
        steady={"unit"},
        labels={"train.read": "fit.read", "train.train": "fit.train"})
    close(out["window_s"], 6, "window")
    close(out["busy_s"], 3, "busy")
    close(out["sections"]["xpass"]["busy_s"], 1, "xpass busy")
    close(out["sections"]["unit"]["busy_s"], 3, "unit busy")
    ops_by_name = dict(out["device_ops"])
    # the xpass op lies outside the steady window: not in the totals
    close(ops_by_name["fusion.1"], 1, "fusion.1 self time in window")
    close(ops_by_name["inner"], 1, "inner")
    close(ops_by_name["fusion.2"], 1, "fusion.2")
    idle = dict(out["idle_gaps"])
    # gaps 0-1 (read), 3-4 (train: midpoint 3.5, the unlabelled child
    # does not hide its parent), 5-6 (train)
    close(idle["fit.read"], 1, "idle in read")
    close(idle["fit.train"], 2, "idle in train")
    close(sum(idle.values()), out["window_s"] - out["busy_s"], "idle sum")
    # two devices: busy is the mean over them
    out2 = tr.reduce_trace(
        {"devices": [ops, [(0, 6 * S, "all")]], "host": host,
         "inventory": {}}, steady={"unit"}, labels={})
    close(out2["busy_s"], (3 + 6) / 2, "mean busy over devices")
    close(dict(out2["idle_gaps"])["between"], 3, "unlabelled gaps")


def check_xpass_bytes():
    # a layout by hand: 6 rows x 10 columns, 4 hot columns in bf16; ELL
    # buckets (2 rows x 1) and (1 row x 2); occurrence buckets (2 cols x 1)
    # and (1 col x 2); 3 lanes
    bf16 = np.dtype("float16")  # two bytes, as bfloat16
    X = types.SimpleNamespace(
        shape=(6, 10),
        dense=np.zeros((6, 4), bf16),
        ell_pcols=(np.zeros((2, 1), np.int32), np.zeros((1, 2), np.int32)),
        ell_vals=(np.zeros((2, 1), bf16), np.zeros((1, 2), bf16)),
        row_pos=np.zeros(6, np.int32),
        bucket_rows=(np.zeros((2, 1), np.int32), np.zeros((1, 2), np.int32)),
        bucket_vals=(np.zeros((2, 1), bf16), np.zeros((1, 2), bf16)))
    got = xpass_evaluation_bytes(X, lanes=3)
    want = {
        "hot_block_twice": 2 * 6 * 4 * 2,              # 96
        "ell_tail_forward": 4 * (4 + 2) + 6 * 4,       # 4 slots + row_pos
        "occ_tail_transposed": 4 * (4 + 2),            # 4 slots
        "w_and_gradient": 2 * 10 * 3 * 4,              # 240
        "margin_write_read": 2 * 6 * 3 * 4,            # 144
        "labels_weights_offsets": 3 * 6 * 4}           # 72
    want["total"] = sum(want.values())                 # 624
    assert got == want, (got, want)
    assert want["total"] == 96 + 48 + 24 + 240 + 144 + 72


def main() -> int:
    for check in (check_intervals, check_self_times, check_reduce,
                  check_xpass_bytes):
        check()
        print(f"ok {check.__name__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
