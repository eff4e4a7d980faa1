"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of unit-testing Spark code on a
local[*] SparkContext (photon-ml SparkTestUtils): we force a fake
8-device CPU platform so every sharding/`psum` path is exercised
without TPU hardware.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# f32 matmuls on CPU for numeric comparisons against scipy/sklearn.
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
# Persistent XLA compilation cache for the suite: the tier-1 wall is
# compile-dominated, so repeat runs load executables from disk instead of
# recompiling. Placement is the repo's one rule (utils/compile_cache.py:
# $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache). Artifacts are
# keyed by jax on program+flags, so numerics are identical to a cold
# compile; only programs over the threshold are stored (tiny jits stay
# out of the cache).
from photon_tpu.utils.compile_cache import (  # noqa: E402
    enable_compilation_cache,
)

enable_compilation_cache(min_compile_secs=0.9)
_cpu_devices = jax.devices("cpu")

import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 suite (-m 'not slow'); "
        "long-running end-to-end checks like the umbrella selfcheck.")
    config.addinivalue_line(
        "markers",
        "tier2: acceptance tests promoted OUT of the tier-1 wall (round-16 "
        "suite-time relief) — statistical end-to-end properties (GP-beats-"
        "random, q-EI-vs-constant-liar, mesh game grids) that each burn "
        "15-60 s re-proving claims the faster unit tests already pin. "
        "Run them with -m tier2 (they implicitly carry `slow`, so the "
        "tier-1 selection -m 'not slow' keeps excluding them).")
    config.addinivalue_line(
        "markers",
        "release_programs: drop this module's compiled XLA programs at "
        "module teardown (jax.clear_caches + photon_tpu program caches). "
        "Apply (pytestmark = pytest.mark.release_programs) to any module "
        "that compiles many multi-device programs: the virtual-CPU XLA "
        "client segfaults compiling LATER unrelated programs once too "
        "many live executables have accumulated in the process "
        "(~460; first seen from test_streamed_mesh's 8-device shard_map "
        "programs breaking test_tuning's GP while_loop compile).")


def pytest_collection_modifyitems(config, items):
    """Every `tier2` item implicitly carries `slow`: tier-2 promotion is
    one marker at the test site, and the long-standing tier-1 selection
    (-m 'not slow') needs no change to exclude the promoted set."""
    for item in items:
        if item.get_closest_marker("tier2") is not None:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs(request):
    """Module teardown for `release_programs`-marked modules: clear the
    photon_tpu module-level jitted-program caches that pin executables
    alive, then jax.clear_caches() — keeping the rest of the suite inside
    the executable-count envelope it had before the marked module ran."""
    yield
    if request.node.get_closest_marker("release_programs") is None:
        return
    streamed = sys.modules.get("photon_tpu.optim.streamed")
    if streamed is not None:
        streamed._MESH_OPS_CACHE.clear()
    random_effect = sys.modules.get("photon_tpu.game.random_effect")
    if random_effect is not None:
        random_effect._SCAN_DISPATCH.clear()
        random_effect._RE_SOLVERS.clear()
        random_effect._FUSED_RE.clear()
    jax.clear_caches()


@pytest.fixture(scope="session", autouse=True)
def _interpret_pallas_kernels():
    """Pallas interpret mode is reachable from tests only: the product
    never interprets a kernel (a dispatched kernel compiles for the
    attached device or fails), so the suite turns the interpreter on for
    itself. tests/test_chip_compile.py compiles the same kernel for the
    described v5e with ``interpret=False``."""
    from photon_tpu.ops import fused

    with fused.interpreted():
        yield


# Tier-1 budget guard: the driver runs the tier-1 selection under
# `timeout -k 10 1470` with six xdist workers, and a pass that lands
# within a minute of the cap is one contended box away from a wall-clock
# kill that reads as a regression. The guard asserts the MEASURED
# headroom stays >= 60 s whenever the canonical tier-1 selection runs
# (full tests/ tree, -m 'not slow', no -k filter) — a breach fails the
# session teardown loudly TODAY, instead of the timeout failing it
# nondeterministically next round. Partial selections (single modules,
# -k filters) never trip it.
TIER1_BUDGET_S = 1470.0
TIER1_MIN_HEADROOM_S = 60.0


@pytest.fixture(scope="session", autouse=True)
def _tier1_budget_guard(request):
    import time as _time

    t0 = _time.time()
    yield
    config = request.config
    if config.option.markexpr != "not slow" or config.option.keyword:
        return
    if getattr(request.session, "testscollected", 0) < 500:
        return  # partial selection: not the tier-1 wall
    wall = _time.time() - t0
    headroom = TIER1_BUDGET_S - wall
    reporter = config.pluginmanager.get_plugin("terminalreporter")
    capman = config.pluginmanager.get_plugin("capturemanager")
    if reporter is not None and capman is not None:
        # fd-level capture is still armed during session-fixture
        # teardown (the output would silently attach to the last item);
        # suspend it so the headroom line lands on the real terminal
        with capman.global_and_fixture_disabled():
            reporter.write_line(
                f"tier-1 wall {wall:.0f}s — {headroom:.0f}s headroom "
                f"against the {TIER1_BUDGET_S:.0f}s budget")
    assert headroom >= TIER1_MIN_HEADROOM_S, (
        f"tier-1 suite burned {wall:.0f}s of the {TIER1_BUDGET_S:.0f}s "
        f"budget — headroom {headroom:.0f}s < {TIER1_MIN_HEADROOM_S:.0f}s "
        f"floor; promote the slowest acceptance tests to tier2 "
        f"(see `--durations=25`) before the timeout kills a round")


@pytest.fixture(scope="session")
def mesh8():
    from photon_tpu.parallel.mesh import make_mesh

    return make_mesh(data_axis="data", devices=_cpu_devices)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
