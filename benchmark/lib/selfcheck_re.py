#!/usr/bin/env python3
"""`re_bytes.py`'s arithmetic on a synthetic bucket plan, in seconds, with no
jax: ``python3 benchmark/lib/selfcheck_re.py`` exits 0 or says what is off."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import re_bytes  # noqa: E402


def main() -> int:
    members = [(1000, 8, 128), (10, 64, 512)]
    jobs = [(4, 256, 1280)]
    assert re_bytes.block_bytes(members) == (1000 * 8 * 128 + 10 * 64 * 512) * 4
    assert re_bytes.block_bytes(jobs) == 4 * 256 * 1280 * 4
    # 20 lock-step iterations: two passes each, and two at the warm start
    assert re_bytes.solve_bytes(jobs, 20) == 4 * 256 * 1280 * 4 * 2 * 21
    assert re_bytes.solve_bytes(jobs, 0) == 2 * re_bytes.block_bytes(jobs)
    mean = re_bytes.update_bytes({"per_user": members, "per_item": jobs}, 20)
    assert mean == (re_bytes.solve_bytes(members, 20)
                    + re_bytes.solve_bytes(jobs, 20)) / 2
    assert re_bytes.update_bytes({}, 20) == 0.0
    # a share of the roofline from these bytes: 1 GB in 2.442 ms is half of
    # 819 GB/s
    share = 100.0 * 1e9 / 2.442e-3 / 819e9
    assert abs(share - 50.0) < 0.01, share
    print("selfcheck_re: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
