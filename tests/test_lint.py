"""photon_tpu.lint: the source-level convention auditor.

Every rule is proven to FIRE on a violating in-memory fixture repo (a
tmp_path tree with just the registries the rules read), the suppression
comment is honored with a reason and rejected without one, the --json
CLI round-trips as a subprocess, and — the tier-1 acceptance — the
repo-wide run exits 0 at HEAD with an EMPTY baseline.

Deliberately jax-free fixtures: the whole module runs in well under a
second, which is what lets the auditor ride tier-1 without budget cost.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from photon_tpu.lint import (Finding, load_baseline, repo_root, run_lint)
from photon_tpu.lint.rules import RULES

REPO = repo_root()


# --------------------------------------------------------------- fixture

_REGISTRIES = {
    "photon_tpu/__init__.py": "",
    "photon_tpu/checkpoint/__init__.py": "",
    "photon_tpu/checkpoint/faults.py": '''
"""sites"""
FAULT_SITES = {"commit": "the commit site", "evaluation": "eval tick"}

def kill_point(site):
    pass
''',
    "photon_tpu/telemetry/__init__.py": '''
"""Counters: the stream family chunk_uploads counter; latency_ gauges;
solve spans."""
TELEMETRY_REGISTRY = {
    "counters": ("stream.chunk_uploads",),
    "gauges": ("serving.latency_*",),
    "span_families": ("solve",),
}
''',
    "photon_tpu/utils/__init__.py": "",
    "photon_tpu/utils/env.py": '''
"""knobs"""
KNOB_DOCS = {"PHOTON_TPU_DEMO": "a demo knob. Owner: demo.py."}

def get_raw(name, default=None):
    import os
    return os.environ.get(name, default)
''',
    "photon_tpu/analysis/__init__.py": "",
    "photon_tpu/analysis/registry.py": '''
HOT_PATH_MODULES = ("photon_tpu.hot",)
''',
    "photon_tpu/profiling/__init__.py": "",
    "photon_tpu/profiling/sentinel.py": '''
_LOWER_BETTER_PATTERNS = ("_ms", "stall")
_EXCLUDE_PATTERNS = ("_n_chips",)
''',
    # a clean module exercising the registries so the clean fixture has
    # no orphan findings
    "photon_tpu/hot.py": '''
from photon_tpu.analysis.contracts import register_contract
from photon_tpu import telemetry
from photon_tpu.checkpoint.faults import kill_point, retry_io
from photon_tpu.utils import env as env_knobs

def touch():
    kill_point("commit")
    retry_io(lambda: 0, site="evaluation")
    telemetry.count("stream.chunk_uploads")
    telemetry.gauge(f"serving.latency_{0}")
    with telemetry.span("solve.demo"):
        pass
    return env_knobs.get_raw("PHOTON_TPU_DEMO")

register_contract(None)
''',
    "bench.py": '''
def main():
    doc = {"legs": {"demo_rate_rows_per_sec": 1.0,
                    "demo_wall_ms": 2.0,
                    "demo_shards_n_chips": 8}}
    return doc

if __name__ == "__main__":
    main()
''',
}


def write_repo(tmp_path, extra=None, replace=None):
    files = dict(_REGISTRIES)
    files.update(replace or {})
    files.update(extra or {})
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def findings_of(report, rule):
    return [f for f in report["findings"] if f.rule == rule]


def run_rules(root, only=None):
    return run_lint(root=root, only=only, baseline=set())


# ---------------------------------------------------------- clean fixture

def test_clean_fixture_has_no_findings(tmp_path):
    report = run_rules(write_repo(tmp_path))
    assert [f.text for f in report["findings"]] == []
    assert report["ok"] and report["n_rules"] == len(RULES) + 1


# ------------------------------------------------------- 1. durable write

class TestDurableWrite:
    def test_fires_on_raw_write(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
import json

def save(path, doc):
    with open(path, "w") as fh:
        json.dump(doc, fh)
'''})
        f, = findings_of(run_rules(root, ["durable_write"]),
                         "durable_write")
        assert f.path == "photon_tpu/bad.py" and "commit_bytes" in f.message

    def test_mode_kw_and_exclusive_create_fire(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    open(path, mode="xb").write(b"")
'''})
        assert findings_of(run_rules(root, ["durable_write"]),
                           "durable_write")

    def test_append_and_read_are_legal(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/ok.py": '''
def log(path):
    open(path, "a").write("event\\n")
    return open(path).read()
'''})
        assert not findings_of(run_rules(root, ["durable_write"]),
                               "durable_write")

    def test_commit_primitive_file_is_exempt(self, tmp_path):
        root = write_repo(tmp_path, extra={
            "photon_tpu/checkpoint/store.py": '''
def commit_bytes(path, data):
    with open(path + ".tmp", "wb") as f:
        f.write(data)
'''})
        assert not findings_of(run_rules(root, ["durable_write"]),
                               "durable_write")

    def test_suppression_with_reason_honored(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    # lint: rawwrite(scratch artifact, nothing resumes from it)
    with open(path, "w") as fh:
        fh.write("x")
'''})
        report = run_rules(root, ["durable_write"])
        assert not findings_of(report, "durable_write")
        assert len(report["suppressed"]) == 1

    def test_suppression_without_reason_rejected(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    # lint: rawwrite()
    with open(path, "w") as fh:
        fh.write("x")
'''})
        report = run_rules(root)
        assert findings_of(report, "durable_write"), \
            "reasonless suppression must not suppress"
        sup, = findings_of(report, "suppression")
        assert "no reason" in sup.message

    def test_wrong_tag_does_not_suppress(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    # lint: unlocked(wrong tag for this rule)
    with open(path, "w") as fh:
        fh.write("x")
'''})
        assert findings_of(run_rules(root, ["durable_write"]),
                           "durable_write")


# -------------------------------------------------- 2. fault-site registry

class TestFaultSiteRegistry:
    def test_undeclared_site_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu.checkpoint.faults import kill_point

def f():
    kill_point("mystery_site")
'''})
        f, = findings_of(run_rules(root, ["fault_site_registry"]),
                         "fault_site_registry")
        assert "mystery_site" in f.message and f.path == "photon_tpu/bad.py"

    def test_retry_io_site_kw_checked(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu.checkpoint.faults import retry_io

def f():
    return retry_io(lambda: 0, site="mystery_io")
'''})
        assert findings_of(run_rules(root, ["fault_site_registry"]),
                           "fault_site_registry")

    def test_orphan_declared_site_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={
            "photon_tpu/checkpoint/faults.py": '''
FAULT_SITES = {"commit": "doc", "evaluation": "doc",
               "ghost_site": "never hit"}

def kill_point(site):
    pass
'''})
        f, = findings_of(run_rules(root, ["fault_site_registry"]),
                         "fault_site_registry")
        assert "ghost_site" in f.message
        assert f.path == "photon_tpu/checkpoint/faults.py"


# ------------------------------------------------------ 3. telemetry sync

class TestTelemetrySync:
    def test_unregistered_counter_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu import telemetry

def f():
    telemetry.count("rogue.counter_nobody_registered")
'''})
        f, = findings_of(run_rules(root, ["telemetry_sync"]),
                         "telemetry_sync")
        assert "rogue.counter_nobody_registered" in f.message

    def test_dynamic_prefix_must_match_glob(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu import telemetry

def f(site):
    telemetry.count(f"rogue.dyn.{site}")
'''})
        f, = findings_of(run_rules(root, ["telemetry_sync"]),
                         "telemetry_sync")
        assert "rogue.dyn." in f.message

    def test_orphan_registry_entry_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={
            "photon_tpu/telemetry/__init__.py": '''
"""chunk_uploads latency_ orphan_counter solve"""
TELEMETRY_REGISTRY = {
    "counters": ("stream.chunk_uploads", "stream.orphan_counter"),
    "gauges": ("serving.latency_*",),
    "span_families": ("solve",),
}
'''})
        f, = findings_of(run_rules(root, ["telemetry_sync"]),
                         "telemetry_sync")
        assert "orphan_counter" in f.message and "nowhere" in f.message

    def test_registry_name_missing_from_docstring_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={
            "photon_tpu/telemetry/__init__.py": '''
"""latency_ solve (chunk uploads described only in prose)"""
TELEMETRY_REGISTRY = {
    "counters": ("stream.chunk_uploads",),
    "gauges": ("serving.latency_*",),
    "span_families": ("solve",),
}
'''})
        f, = findings_of(run_rules(root, ["telemetry_sync"]),
                         "telemetry_sync")
        assert "docstring" in f.message and "chunk_uploads" in f.message

    def test_unknown_span_family_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu import telemetry

def f():
    with telemetry.span("rogue_family.phase"):
        pass
'''})
        f, = findings_of(run_rules(root, ["telemetry_sync"]),
                         "telemetry_sync")
        assert "rogue_family" in f.message

    @pytest.mark.parametrize("call,word", [
        ('telemetry.count_device("rogue.device_counter", x)',
         "rogue.device_counter"),
        ('telemetry.device_scope("rogue.scope")', "rogue.scope"),
        ('device_scope("xpass.fwd")', None),
    ], ids=["count_device", "unregistered_scope", "registered_scope"])
    def test_device_counters_and_scopes_are_held_too(self, tmp_path, call,
                                                     word):
        root = write_repo(tmp_path, replace={
            "photon_tpu/telemetry/__init__.py": '''
"""chunk_uploads latency_ solve; device scope xpass.fwd"""
TELEMETRY_REGISTRY = {
    "counters": ("stream.chunk_uploads",),
    "gauges": ("serving.latency_*",),
    "span_families": ("solve",),
    "device_scopes": ("xpass.fwd",),
}
'''}, extra={"photon_tpu/bad.py": f'''
from photon_tpu import telemetry
from photon_tpu.telemetry import device_scope

def f(x):
    with device_scope("xpass.fwd"):
        {call}
'''})
        found = findings_of(run_rules(root, ["telemetry_sync"]),
                            "telemetry_sync")
        if word is None:
            assert not found
        else:
            f, = found
            assert word in f.message

    def test_selftest_mains_are_exempt(self, tmp_path):
        root = write_repo(tmp_path, extra={
            "photon_tpu/demo/__init__.py": "",
            "photon_tpu/demo/__main__.py": '''
from photon_tpu import telemetry

def run_selftest():
    telemetry.count("selftest.scratch_counter")
'''})
        assert not findings_of(run_rules(root, ["telemetry_sync"]),
                               "telemetry_sync")


# ----------------------------------------------------- 4. lock discipline

_LOCKED_CLASS = '''
import threading

class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0
        self.generation = 0

    def bump(self, v):
        with self._lock:
            self.total += v

    def unsafe_reset(self):{marker}
        self.total = 0
'''


class TestLockDiscipline:
    def test_mixed_locked_unlocked_write_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={
            "photon_tpu/rec.py": _LOCKED_CLASS.format(marker="")})
        f, = findings_of(run_rules(root, ["lock_discipline"]),
                         "lock_discipline")
        assert "Recorder.total" in f.message and "unsafe_reset" in f.message

    def test_init_writes_do_not_count(self, tmp_path):
        # generation is written only in __init__ + nowhere else: clean
        root = write_repo(tmp_path, extra={"photon_tpu/rec.py": '''
import threading

class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0

    def bump(self, v):
        with self._lock:
            self.total += v
'''})
        assert not findings_of(run_rules(root, ["lock_discipline"]),
                               "lock_discipline")

    def test_suppression_with_reason_honored(self, tmp_path):
        body = _LOCKED_CLASS.format(
            marker="\n        # lint: unlocked(reset runs pre-start, "
                   "single-threaded by construction)")
        root = write_repo(tmp_path, extra={"photon_tpu/rec.py": body})
        report = run_rules(root, ["lock_discipline"])
        assert not findings_of(report, "lock_discipline")
        assert report["suppressed"]


# --------------------------------------------------- 5. env-knob registry

class TestEnvKnobRegistry:
    def test_adhoc_environ_read_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
import os

def f():
    return os.environ.get("PHOTON_TPU_DEMO", "auto")
'''})
        f, = findings_of(run_rules(root, ["env_knob_registry"]),
                         "env_knob_registry")
        assert "ad-hoc" in f.message and "get_raw" in f.message

    def test_undeclared_knob_literal_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu.utils import env as env_knobs

KNOB = "PHOTON_TPU_BRAND_NEW_KNOB"
'''})
        f, = findings_of(run_rules(root, ["env_knob_registry"]),
                         "env_knob_registry")
        assert "PHOTON_TPU_BRAND_NEW_KNOB" in f.message

    def test_orphan_declared_knob_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={"photon_tpu/utils/env.py": '''
"""knobs"""
KNOB_DOCS = {"PHOTON_TPU_DEMO": "read by hot.py",
             "PHOTON_TPU_GHOST": "read by nobody"}

def get_raw(name, default=None):
    import os
    return os.environ.get(name, default)
'''})
        f, = findings_of(run_rules(root, ["env_knob_registry"]),
                         "env_knob_registry")
        assert "PHOTON_TPU_GHOST" in f.message

    def test_environ_subscript_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
import os

def f():
    os.environ["PHOTON_TPU_DEMO"] = "on"
'''})
        assert findings_of(run_rules(root, ["env_knob_registry"]),
                           "env_knob_registry")


# -------------------------------------------------- 6. contract coverage

class TestContractCoverage:
    def test_specless_listed_module_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={"photon_tpu/hot.py": '''
from photon_tpu import telemetry
from photon_tpu.checkpoint.faults import kill_point, retry_io
from photon_tpu.utils import env as env_knobs

def touch():
    kill_point("commit")
    retry_io(lambda: 0, site="evaluation")
    telemetry.count("stream.chunk_uploads")
    telemetry.gauge(f"serving.latency_{0}")
    with telemetry.span("solve.demo"):
        pass
    return env_knobs.get_raw("PHOTON_TPU_DEMO")
'''})
        f, = findings_of(run_rules(root, ["contract_coverage"]),
                         "contract_coverage")
        assert "photon_tpu.hot" in f.message and "no ContractSpec" \
            in f.message

    def test_unlisted_registering_module_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/rogue.py": '''
from photon_tpu.analysis.contracts import register_contract

register_contract(None)
'''})
        f, = findings_of(run_rules(root, ["contract_coverage"]),
                         "contract_coverage")
        assert "photon_tpu.rogue" in f.message \
            and "HOT_PATH_MODULES" in f.message


# -------------------------------------------------- 7. sentinel coverage

class TestSentinelCoverage:
    def test_cost_leg_gated_higher_better_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={"bench.py": '''
def main():
    doc = {"legs": {"demo_commit_latency_us": 3.0}}
    return doc

if __name__ == "__main__":
    main()
'''})
        f, = findings_of(run_rules(root, ["sentinel_coverage"]),
                         "sentinel_coverage")
        assert "demo_commit_latency_us" in f.message \
            and "lower-better" in f.message

    def test_config_leg_gated_fires(self, tmp_path):
        root = write_repo(tmp_path, replace={"bench.py": '''
def main():
    doc = {"legs": {"demo_mesh_n_chips_used": 8}}
    return doc

if __name__ == "__main__":
    main()
'''})
        # "_n_chips" excluded in the fixture sentinel only as exact
        # substring: "demo_mesh_n_chips_used" contains it -> excluded,
        # so use a count leg the exclude list misses
        root = write_repo(tmp_path, replace={"bench.py": '''
def main():
    doc = {"legs": {"demo_run_snapshots": 8}}
    return doc

if __name__ == "__main__":
    main()
'''})
        f, = findings_of(run_rules(root, ["sentinel_coverage"]),
                         "sentinel_coverage")
        assert "demo_run_snapshots" in f.message

    def test_spread_stats_dict_is_resolved(self, tmp_path):
        root = write_repo(tmp_path, replace={"bench.py": '''
def demo_problem():
    stats = {"demo_layout_pad_stall_pct": 0.5}
    return object(), stats

def main():
    batch, demo_stats = demo_problem()
    doc = {"legs": {"demo_rate_rows_per_sec": 1.0, **demo_stats}}
    return doc

if __name__ == "__main__":
    main()
'''})
        # "stall" IS lower-better in the fixture patterns: clean…
        assert not findings_of(run_rules(root, ["sentinel_coverage"]),
                               "sentinel_coverage")
        # …but a cost-shaped spread leg the patterns miss fires
        root = write_repo(tmp_path, replace={"bench.py": '''
def demo_problem():
    stats = {"demo_layout_pad_overhead_us": 0.5}
    return object(), stats

def main():
    batch, demo_stats = demo_problem()
    doc = {"legs": {"demo_rate_rows_per_sec": 1.0, **demo_stats}}
    return doc

if __name__ == "__main__":
    main()
'''})
        f, = findings_of(run_rules(root, ["sentinel_coverage"]),
                         "sentinel_coverage")
        assert "demo_layout_pad_overhead_us" in f.message


# ----------------------------------------------------- 8. spawn hygiene

class TestSpawnHygiene:
    def test_unguarded_spawn_script_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"benches/pool_script.py": '''
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

def work():
    with ProcessPoolExecutor(
            mp_context=multiprocessing.get_context("spawn")) as pool:
        return pool

work()
'''})
        f, = findings_of(run_rules(root, ["spawn_hygiene"]),
                         "spawn_hygiene")
        assert "__main__" in f.message

    def test_guarded_spawn_script_clean(self, tmp_path):
        root = write_repo(tmp_path, extra={"benches/pool_script.py": '''
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

def work():
    with ProcessPoolExecutor(
            mp_context=multiprocessing.get_context("spawn")) as pool:
        return pool

if __name__ == "__main__":
    work()
'''})
        assert not findings_of(run_rules(root, ["spawn_hygiene"]),
                               "spawn_hygiene")

    def test_daemon_thread_without_join_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bg.py": '''
import threading

class Loop:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        pass
'''})
        f, = findings_of(run_rules(root, ["spawn_hygiene"]),
                         "spawn_hygiene")
        assert "daemon thread" in f.message

    def test_nondaemon_thread_unjoined_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bg.py": '''
import threading

def fan_out(fn):
    ts = [threading.Thread(target=fn) for _ in range(4)]
    for t in ts:
        t.start()
'''})
        f, = findings_of(run_rules(root, ["spawn_hygiene"]),
                         "spawn_hygiene")
        assert "not joined" in f.message

    def test_joined_threads_clean(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bg.py": '''
import threading

def fan_out(fn):
    ts = [threading.Thread(target=fn) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
'''})
        assert not findings_of(run_rules(root, ["spawn_hygiene"]),
                               "spawn_hygiene")

    def test_executor_without_shutdown_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bg.py": '''
from concurrent.futures import ThreadPoolExecutor

class Fleet:
    def __init__(self):
        self._pool = ThreadPoolExecutor(max_workers=2)
'''})
        f, = findings_of(run_rules(root, ["spawn_hygiene"]),
                         "spawn_hygiene")
        assert "shutdown" in f.message


# -------------------------------------------------- 9. exception hygiene

class TestExceptionHygiene:
    def test_broad_swallow_around_fault_site_fires(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
from photon_tpu.checkpoint.faults import kill_point

def f():
    try:
        kill_point("commit")
    except Exception:
        return None
'''})
        f, = findings_of(run_rules(root, ["exception_hygiene"]),
                         "exception_hygiene")
        assert "InjectedFault" in f.message

    def test_injectedfault_reraise_first_is_clean(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/ok.py": '''
from photon_tpu.checkpoint.faults import InjectedFault, kill_point

def f():
    try:
        kill_point("commit")
    except InjectedFault:
        raise
    except Exception:
        return None
'''})
        assert not findings_of(run_rules(root, ["exception_hygiene"]),
                               "exception_hygiene")

    def test_delivering_handler_is_clean(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/ok.py": '''
from photon_tpu.checkpoint.faults import kill_point

def f(fut):
    try:
        kill_point("commit")
    except BaseException as e:
        fut.set_exception(e)
'''})
        assert not findings_of(run_rules(root, ["exception_hygiene"]),
                               "exception_hygiene")

    def test_narrow_handler_is_clean(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/ok.py": '''
from photon_tpu.checkpoint.faults import retry_io

def f():
    try:
        return retry_io(lambda: 0, site="evaluation")
    except OSError:
        return None
'''})
        assert not findings_of(run_rules(root, ["exception_hygiene"]),
                               "exception_hygiene")

    def test_suppression_with_reason_honored(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/ok.py": '''
from photon_tpu.checkpoint.faults import kill_point

def f():
    try:
        kill_point("commit")
    # lint: swallow(the injected death IS the degrade path under test)
    except BaseException:
        return None
'''})
        report = run_rules(root, ["exception_hygiene"])
        assert not findings_of(report, "exception_hygiene")
        assert report["suppressed"]


# ----------------------------------------------------- engine mechanics

class TestEngine:
    def test_baseline_subtracts_by_fingerprint(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    with open(path, "w") as fh:
        fh.write("x")
'''})
        f, = findings_of(run_lint(root=root, baseline=set()),
                         "durable_write")
        report = run_lint(root=root, baseline={f.fingerprint})
        assert not findings_of(report, "durable_write")

    def test_shipped_baseline_is_empty(self):
        assert load_baseline() == set()

    def test_only_filters_rules(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
import os

def f():
    with open("x", "w") as fh:
        fh.write(os.environ.get("PHOTON_TPU_DEMO", ""))
'''})
        report = run_rules(root, ["env_knob_registry"])
        assert findings_of(report, "env_knob_registry")
        assert not findings_of(report, "durable_write")

    def test_syntax_error_reported_not_crashed(self, tmp_path):
        root = write_repo(tmp_path, extra={
            "photon_tpu/broken.py": "def f(:\n"})
        report = run_rules(root)
        f, = findings_of(report, "parse")
        assert f.path == "photon_tpu/broken.py"

    def test_finding_roundtrip(self):
        f = Finding("durable_write", "a.py", 3, "msg", key="k")
        assert f.to_json()["key"] == "k"
        assert "a.py:3" in f.text


# ------------------------------------------------ the repo itself + CLI

@pytest.mark.filterwarnings("ignore")
class TestRepoIsClean:
    def test_repo_wide_run_exits_clean_at_head(self):
        """THE acceptance pin: the auditor finds nothing at HEAD with an
        empty baseline — drift from any registered convention turns
        tier-1 red in milliseconds."""
        report = run_lint(root=REPO, baseline=set())
        assert [f.text for f in report["findings"]] == []
        assert report["n_rules"] == len(RULES) + 1
        assert report["n_files"] > 100

    def test_every_suppression_in_repo_carries_a_reason(self):
        from photon_tpu.lint import load_context

        ctx = load_context(REPO)
        n = 0
        for rel, src in ctx.files.items():
            assert not src.bad_suppressions, (rel, src.bad_suppressions)
            n += len(src.suppressions)
        assert n >= 5  # the documented deliberate sites

    def test_json_cli_subprocess(self):
        """--json CLI e2e: one machine-readable object, exit 0 at HEAD."""
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.lint", "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True and doc["n_findings"] == 0
        assert doc["n_rules"] == len(RULES) + 1

    def test_cli_exit_1_on_findings(self, tmp_path):
        root = write_repo(tmp_path, extra={"photon_tpu/bad.py": '''
def save(path):
    with open(path, "w") as fh:
        fh.write("x")
'''})
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.lint", "--json",
             "--root", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert doc["n_findings"] == 1
        assert doc["findings"][0]["rule"] == "durable_write"

    def test_bench_guard_matches_registry_counts(self):
        """bench.py --check-lint is wired before the jax imports (the
        --check-contracts precedent) — prove by text, not subprocess
        (the full bench import would cost minutes)."""
        with open(os.path.join(REPO, "bench.py")) as fh:
            src = fh.read()
        guard = src.index('"--check-lint" in sys.argv')
        assert guard < src.index("import jax")

    def test_lint_is_a_selfcheck_suite(self):
        from photon_tpu.__main__ import SUITES

        names = [n for n, _ in SUITES]
        # round 18: + the whole-program concurrency auditor (threads);
        # PR 29: - the kernels suite, deleted with the kernels
        assert "lint" in names and "threads" in names and len(names) == 12
