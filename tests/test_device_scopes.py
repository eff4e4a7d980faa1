"""Named device scopes inside the compiled solves, the on-device evaluation
count, `count_device`, and the benchmark's per-scope trace reduction."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import telemetry
from photon_tpu.data.dataset import make_batch
from photon_tpu.data.matrix import _contract_blocked_ell
from photon_tpu.models.training import (
    _static_config,
    _train_run,
    _train_run_grid_lanes,
    lane_weight_arrays,
    make_objective,
    train_glm,
    train_glm_grid,
)
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops import lane_objective
from photon_tpu.ops.losses import TaskType
from photon_tpu.ops.objective import Objective
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.lane_lbfgs import minimize_lbfgs_margin_lanes
from photon_tpu.optim.lbfgs import minimize_lbfgs, minimize_lbfgs_margin
from photon_tpu.optim.regularization import l2

LOGISTIC = TaskType.LOGISTIC_REGRESSION
XPASS = {s for s in telemetry.DEVICE_SCOPES if s.startswith("xpass.")}
# the scopes of a GLM solve; the coordinate-descent phases (`game*`) wrap
# whole updates and are pinned by tests/test_glmix_wide.py
# — and `mesh.psum` is entered only where an objective has an axis name:
# no one-device program carries it (tests/test_mesh_cell.py pins where it is)
MESH = {"mesh.psum"}
EVERY = {s for s in telemetry.DEVICE_SCOPES
         if not s.startswith("game")} - MESH


def _cfg(**kw):
    return OptimizerConfig(**{"max_iters": 6, "tolerance": 1e-7,
                              "reg": l2(), "history": 3, **kw})


def _bell_batch():
    X = _contract_blocked_ell()
    y = (np.random.default_rng(1).uniform(size=X.shape[0]) < 0.5)
    return make_batch(X, y.astype(np.float32))


def _dense_batch(n=64, d=8):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + rng.normal(size=n) > 0)
    return make_batch(X, y.astype(np.float32))


# ------------------------------------------------ (a) scopes in the programs
def _lane_program():
    b, cfg = _bell_batch(), _cfg(reg_weight=0.0)
    l2s, _, static_cfg = lane_weight_arrays(cfg, [0.1, 1.0])
    obj = make_objective(LOGISTIC, cfg, b.X.shape[1])
    w0 = jnp.zeros((b.X.shape[1],), jnp.float32)
    return (lambda b, w, o, l2v: _train_run_grid_lanes(
        b, w, o, l2v, None, static_cfg)), (b, w0, obj, l2s)


def _scalar_program():
    b, cfg = _bell_batch(), _cfg(reg_weight=0.5)
    obj = make_objective(LOGISTIC, cfg, b.X.shape[1])
    w0 = jnp.zeros((b.X.shape[1],), jnp.float32)
    return (lambda b, w, o: _train_run(
        b, w, o, None, _static_config(cfg),
        VarianceComputationType.NONE)), (b, w0, obj)


def _value_and_grad_program():
    b, cfg = _bell_batch(), _cfg(reg_weight=0.5)
    obj = make_objective(LOGISTIC, cfg, b.X.shape[1])
    w0 = jnp.zeros((b.X.shape[1],), jnp.float32)
    return (lambda o, b, w: o.value_and_grad(w, b)), (obj, b, w0)


def _chunk_view_program():
    """The same evaluation over a shard / chunk view: its buckets are
    padded to a ladder shared across shards, so its rows stay in the
    caller's order and the forward tail is reassembled by a gather."""
    from photon_tpu.data.matrix import SparseRows, shard_blocked_ell

    rng = np.random.default_rng(0)
    n, d, k = 64, 96, 6
    col = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % (d - 1)
    X = shard_blocked_ell(
        SparseRows(col.astype(np.int32),
                   rng.normal(size=(n, k)).astype(np.float32), d),
        2, d_dense=16).chunk(0)
    assert X.row_order is None
    b = make_batch(X, (rng.uniform(size=n // 2) < 0.5).astype(np.float32))
    obj = make_objective(LOGISTIC, _cfg(reg_weight=0.5), d)
    return (lambda o, b, w: o.value_and_grad(w, b)), (
        obj, b, jnp.zeros((d,), jnp.float32))


REASSEMBLE = {"xpass.fwd.reassemble"}


@pytest.mark.parametrize("build,expected,absent", [
    (_lane_program, EVERY - REASSEMBLE, REASSEMBLE),
    (_scalar_program, EVERY - REASSEMBLE, REASSEMBLE),
    (_value_and_grad_program, (XPASS | {"objective.loss"}) - REASSEMBLE,
     REASSEMBLE),
    (_chunk_view_program, XPASS | {"objective.loss"}, set()),
], ids=["lane_solve", "scalar_margin_solve", "value_and_grad",
        "value_and_grad_chunk_view"])
def test_scopes_reach_the_compiled_program(build, expected, absent):
    """A `to_blocked_ell` layout stores its rows in concatenation order:
    no program over it has a reassembly. A chunk view still does."""
    fn, args = build()
    text = jax.jit(fn).lower(*args).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert op_names
    seen = {s for s in telemetry.DEVICE_SCOPES
            if any(s in part for name in op_names
                   for part in name.split("/"))}
    assert expected <= seen, sorted(expected - seen)
    assert not ((absent | MESH) & seen), sorted((absent | MESH) & seen)
    if not {"lbfgs.push"} <= expected:  # a bare evaluation: no solver phase
        assert not {s for s in seen if s.startswith(("lbfgs.", "solve."))}


def test_device_scope_refuses_an_unregistered_name():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        telemetry.device_scope("typo")
    with telemetry.device_scope("xpass.fwd"):
        pass


# ------------------------------------------- (b) the reduction's arithmetic
def test_scope_reduce_self_times_and_chains():
    from benchmark.lib import scope_reduce as sr

    scopes = telemetry.DEVICE_SCOPES
    root = "jit(_train_run)/while"
    body = root + "/body/"
    ops = [
        # a while over [100, 1100): two scoped children, one unscoped
        (100.0, 1000.0, root + ":", "%while"),
        (150.0, 300.0, body + "xpass.fwd/xpass.fwd.tail/gather:", "%g"),
        (500.0, 200.0,
         body + "transpose(jvp(xpass.fwd))/xpass.fwd.tail/scatter-add:",
         "%s"),
        (800.0, 100.0, "", "%copy.7 = f32[8] copy(f32[8] %param.3)"),
        # a nested search loop: its own 50 ns, and a loss pass inside it
        (1200.0, 250.0, body + "lbfgs.linesearch/while:", "%while.2"),
        (1250.0, 200.0,
         body + "lbfgs.linesearch/while/body/objective.loss/mul:", "%m"),
        # outside every window
        (5000.0, 400.0, body + "lbfgs.push/dot_general:", "%late"),
    ]
    table = sr.reduce_events([ops], [(0.0, 2000.0)], scopes)
    ns = {k: round(v * 1e9, 6) for k, v in table["scopes"].items()}
    assert ns == {"xpass.fwd.tail": 500.0, "unscoped": 500.0,
                  "lbfgs.linesearch": 50.0, "objective.loss": 200.0}
    # self times add up to the union of the intervals
    assert sum(ns.values()) == pytest.approx(table["busy_s"] * 1e9)
    assert table["busy_s"] * 1e9 == pytest.approx(1250.0)
    assert table["chains"]["lbfgs.linesearch>objective.loss"] \
        == pytest.approx(200e-9)
    assert table["chains"]["xpass.fwd>xpass.fwd.tail"] == pytest.approx(
        500e-9)
    assert table["unscoped_ops"][0] == ["%while", pytest.approx(400e-9)]
    assert table["unscoped_ops"][1][0].startswith("%copy.7")
    # a compiler-made op with no op_name goes where its operand was made;
    # one fed by a loop-carried buffer (above: %param.3) stays unscoped
    made = sr.event_chains([
        (0.0, 10.0, "jit(f)/xpass.fwd/xpass.fwd.tail/gather:",
         "%fusion.160 = bf16[64,8]{0,1} fusion(f32[9]{0} %w, s32[64]{0} %i)"),
        (10.0, 5.0, "", "%copy.233 = f32[64,8]{1,0} copy(bf16[64,8]{0,1} "
                        "%fusion.160)"),
        (15.0, 5.0, "", "%reshape.9 = f32[32,2,8]{2,1,0} reshape("
                        "f32[64,8]{1,0} %copy.233)"),
    ], scopes)
    assert [c for _, _, c, _ in made] == [("xpass.fwd", "xpass.fwd.tail")] * 3
    assert sr.scope_chain(
        "jit(f)/transpose(jvp(xpass.t))/xpass.t.hot/dot_general:",
        scopes) == ("xpass.t", "xpass.t.hot")
    assert sr.scope_chain("jit(f)/while/body/add:", scopes) == ()


def test_scope_reduce_reads_a_profiler_trace(tmp_path):
    """The wire-format reader against jax's own: the same host annotation
    at the same offset from the trace's other events."""
    from jax.profiler import ProfileData

    from benchmark.lib import scope_reduce as sr
    from benchmark.lib.trace_reduce import newest_xplane

    with telemetry.device_trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.section.unit"):
            with jax.profiler.TraceAnnotation("inner.mark"):
                jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    path = newest_xplane(str(tmp_path))
    mine = {name: (s, d) for s, d, name in sr.load(path)["host"]
            if name in ("bench.section.unit", "inner.mark")}
    theirs = {e.name: (e.start_ns, e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name in ("bench.section.unit", "inner.mark")}
    assert set(mine) == set(theirs) == {"bench.section.unit", "inner.mark"}
    for name in mine:
        assert mine[name][1] == pytest.approx(theirs[name][1], abs=1.0)
    assert mine["inner.mark"][0] - mine["bench.section.unit"][0] \
        == pytest.approx(theirs["inner.mark"][0]
                         - theirs["bench.section.unit"][0], abs=1.0)
    # a CPU trace has no device plane and no op_name: nothing to report
    assert sr.load(path)["devices"] == []


# ------------------------------------ (c) the evaluation count is the truth
@pytest.mark.parametrize("solver", ["scalar_margin", "lanes", "generic"])
def test_evaluations_count_the_line_search_calls(solver, monkeypatch):
    b = _dense_batch()
    cfg = _cfg(reg_weight=0.5, max_iters=8)
    obj = make_objective(LOGISTIC, cfg, 8)
    calls = {"n": 0}

    def counting(fn):
        def wrapped(*a, **k):
            calls["n"] += 1
            return fn(*a, **k)
        return wrapped

    with jax.disable_jit():  # the loops run in Python: calls are countable
        if solver == "scalar_margin":
            monkeypatch.setattr(Objective, "phi_at_ray",
                                counting(Objective.phi_at_ray))
            res = minimize_lbfgs_margin(obj, b, jnp.zeros((8,)),
                                        max_iters=8, history=3)
            searched = calls["n"]
        elif solver == "lanes":
            monkeypatch.setattr(lane_objective, "phi_at_ray_lanes",
                                counting(lane_objective.phi_at_ray_lanes))
            res = minimize_lbfgs_margin_lanes(
                obj, jnp.asarray([0.1, 1.0, 10.0]), b, jnp.zeros((8, 3)),
                max_iters=8, history=3)
            searched = calls["n"]
        else:
            vg = counting(lambda w: obj.value_and_grad(w, b))
            res = minimize_lbfgs(vg, jnp.zeros((8,)), max_iters=8,
                                 history=3)
            # one evaluation at w0, one at every accepted point
            searched = calls["n"] - 1 - int(res.iterations)
    assert int(np.max(res.iterations)) >= 3
    assert int(res.evaluations) == searched
    assert searched >= int(np.max(res.iterations))


# ------------------------------------------------- (d) no readback to count
def test_count_device_reads_back_only_for_the_report():
    b = _dense_batch()
    cfg = _cfg(reg_weight=0.5, max_iters=8)
    train_glm(b, LOGISTIC, cfg)  # compile outside the guard
    train_glm_grid(b, LOGISTIC, cfg, [0.1, 1.0], device_results=True)
    with telemetry.run("scopes") as run:
        with jax.transfer_guard_device_to_host("disallow"):
            _, res = train_glm(b, LOGISTIC, cfg)
            grid, _ = train_glm_grid(b, LOGISTIC, cfg, [0.1, 1.0],
                                     device_results=True)
            telemetry.count_device("solver.evaluations", jnp.arange(4))
            assert "solver.iterations" not in run.counters
        counters = run.report_compact()["counters"]
    assert counters["solver.iterations"] == float(
        int(res.iterations) + int(np.max(grid.iterations)))
    assert counters["solver.linesearch_trials"] == float(
        int(res.evaluations) + int(grid.evaluations))
    assert counters["solver.evaluations"] == 6.0  # the default reduce: sum
    with pytest.raises(ValueError, match="sum"):
        run.count_device("solver.iterations", jnp.zeros(()), reduce="mean")
    telemetry.count_device("solver.iterations", jnp.ones(()))  # no run: no-op


# --------------------------------------------- (e) scopes add no primitive
def test_telemetry_off_is_still_free():
    from photon_tpu.analysis.contracts import REGISTRY, check_contract

    assert check_contract(REGISTRY["telemetry_off_is_free"]) == []
