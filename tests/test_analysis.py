"""The jaxpr contract checker itself (photon_tpu/analysis): walker
recursion through every higher-order primitive, and one known-VIOLATION
fixture per rule — each of the five rules must provably fire on a program
that breaks its contract, or the zero-violation registry check means
nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from photon_tpu.analysis import (
    ContractSpec,
    TraceSignatureLog,
    check_contract,
    collective_counts,
    const_bytes,
    count_primitives,
    sites,
    trace_signature,
    weak_type_drift,
)
from photon_tpu.parallel.mesh import make_mesh, shard_map

# Trace-heavy, not compile-heavy — but a handful of fixtures do build
# shard_map programs; keep the suite's executable envelope tidy anyway.
pytestmark = pytest.mark.release_programs


def _violations(build, rule=None, **spec_kw):
    spec = ContractSpec(name="fixture", build=build, **spec_kw)
    out = check_contract(spec)
    if rule is None:
        return out
    return [v for v in out if v.rule == rule]


# ------------------------------------------------------------------ walker
class TestWalker:
    def test_nested_scan_in_while_in_jit(self):
        """The canonical solver nesting: jit(while(scan(...))) — the
        walker finds primitives at every level and reports loop depth."""

        def scan_body(c, _):
            return c * 2.0, jnp.sin(c)

        def while_body(c):
            c2, s = lax.scan(scan_body, c, None, length=3)
            return c2 + jnp.sum(s) + jnp.cos(c2)

        @jax.jit
        def f(x):
            return lax.while_loop(lambda c: jnp.sum(c) < 10.0, while_body,
                                  jnp.tanh(x))

        jaxpr = jax.make_jaxpr(f)(jnp.ones(3))
        counts = count_primitives(jaxpr)
        assert counts["sin"] == 1 and counts["cos"] == 1 \
            and counts["tanh"] == 1
        depth = {s.name: s.loop_depth for s in sites(jaxpr)}
        assert depth["tanh"] == 0  # jit does not multiply execution
        assert depth["cos"] == 1  # while body
        assert depth["sin"] == 2  # scan inside while
        paths = {s.name: s.path for s in sites(jaxpr)}
        assert paths["sin"] == ("jit", "while", "scan")

    def test_cond_branches(self):
        """`cond` carries its branches as a TUPLE param — both must be
        walked (the naive params.values() isinstance walk misses them)."""

        def f(x):
            return lax.cond(jnp.sum(x) > 0,
                            lambda z: jnp.sin(z), lambda z: jnp.cos(z), x)

        counts = count_primitives(jax.make_jaxpr(f)(jnp.ones(3)))
        assert counts["sin"] == 1 and counts["cos"] == 1

    def test_shard_map_sub_jaxpr(self, mesh8):
        def f(x):
            return shard_map(lambda v: lax.psum(jnp.sin(v), "data"),
                             mesh=mesh8, in_specs=P("data"),
                             out_specs=P())(x)

        jaxpr = jax.make_jaxpr(f)(jnp.ones(16))
        assert collective_counts(jaxpr) == {"psum": 1}
        assert count_primitives(jaxpr)["sin"] == 1
        (site,) = [s for s in sites(jaxpr) if s.name == "psum"]
        assert "shard_map" in site.path

    def test_custom_vjp_branch(self):
        @jax.custom_vjp
        def f(x):
            return jnp.sin(x)

        def fwd(x):
            return jnp.sin(x), x

        def bwd(res, ct):
            return (ct * jnp.cos(res),)

        f.defvjp(fwd, bwd)
        # primal trace: the walker descends into fun_jaxpr
        counts = count_primitives(jax.make_jaxpr(lambda x: f(x * 2.0))(
            jnp.ones(3)))
        assert counts["sin"] == 1
        # grad trace: the bwd branch's cos is reachable too
        counts_g = count_primitives(jax.make_jaxpr(
            jax.grad(lambda x: jnp.sum(f(x))))(jnp.ones(3)))
        assert counts_g["cos"] == 1

    def test_const_bytes(self):
        big = np.ones((1024, 256), np.float32)  # 1 MiB closure

        jaxpr = jax.make_jaxpr(lambda x: x @ jnp.asarray(big))(
            jnp.ones(1024))
        assert const_bytes(jaxpr) >= big.nbytes


# ----------------------------------------------- rule violation fixtures
class TestRuleFires:
    def test_collective_budget_overrun(self, mesh8):
        """Two psums against a one-psum budget: the streamed regression
        this rule exists for (a psum inside a chunk partial)."""

        def build():
            def body(v):
                return lax.psum(v, "data") + lax.psum(v * v, "data")

            fn = lambda x: shard_map(body, mesh=mesh8,  # noqa: E731
                                     in_specs=P("data"),
                                     out_specs=P("data"))(x)
            return fn, (jnp.ones(16),)

        out = _violations(build, "collective-budget",
                          collectives={"psum": 1})
        assert out and "2 `psum` against a budget of 1" in out[0].message

    def test_collective_budget_unexpected_kind(self, mesh8):
        """An all_gather nobody declared is drift even when psum matches."""

        def build():
            def body(v):
                # all_gather's result is typed varying, so the output
                # stays per-shard (out_specs=P("data"))
                return v * (jnp.sum(lax.all_gather(v, "data")) + lax.psum(
                    jnp.sum(v), "data"))

            fn = lambda x: shard_map(body, mesh=mesh8,  # noqa: E731
                                     in_specs=P("data"),
                                     out_specs=P("data"))(x)
            return fn, (jnp.ones(16),)

        out = _violations(build, "collective-budget",
                          collectives={"psum": 1})
        assert out and "all_gather" in out[0].message

    def test_forbidden_primitive(self):
        def build():
            idx = jnp.zeros((4, 1), jnp.int32)
            fn = lambda x: x.at[idx[:, 0]].add(1.0)  # noqa: E731
            return fn, (jnp.ones(8),)

        out = _violations(build, "collective-budget",
                          forbid=("scatter-add",))
        assert out and "scatter-add" in out[0].message

    def test_transfer_lint_callback_in_loop(self):
        """A host callback inside a scan body: a round-trip per
        iteration, the exact anti-pattern the rule names."""

        def build():
            def body(c, _):
                v = jax.pure_callback(
                    np.sin, jax.ShapeDtypeStruct((), jnp.float32), c)
                return c + v, None

            fn = lambda x: lax.scan(body, x, None, length=3)[0]  # noqa: E731
            return fn, (jnp.float32(1.0),)

        out = _violations(build, "transfer-lint")
        assert out and "EVERY iteration" in out[0].message

    def test_transfer_lint_device_put(self):
        def build():
            fn = lambda x: jax.device_put(x) + 1.0  # noqa: E731
            return fn, (jnp.ones(4),)

        assert _violations(build, "transfer-lint")

    def test_dtype_policy_f64_leak(self):
        def build():
            fn = lambda x: jnp.sum(x.astype(jnp.float64))  # noqa: E731
            return fn, (jnp.ones(4),)

        with jax.enable_x64(True):
            out = _violations(build, "dtype-policy")
        assert out and "float64" in out[0].message

    def test_dtype_policy_bf16_accumulation(self):
        """jnp.sum upcasts bf16 itself, so the reachable bf16 accumulators
        are cumsum-style scans (and bf16 psums) — cumsum stays bf16."""

        def build():
            fn = lambda x: x.cumsum()[-1]  # noqa: E731
            return fn, (jnp.ones(64, jnp.bfloat16),)

        out = _violations(build, "dtype-policy")
        assert out and "bfloat16" in out[0].message

    def test_dtype_policy_bf16_matmul_needs_f32_out(self):
        def build():
            fn = lambda a, b: a @ b  # bf16 x bf16 -> bf16  # noqa: E731
            return fn, (jnp.ones((8, 4), jnp.bfloat16),
                        jnp.ones((4, 8), jnp.bfloat16))

        out = _violations(build, "dtype-policy")
        assert out and "preferred_element_type" in out[0].message
        # the policy-compliant form is clean: bf16 in, f32 accumulate
        ok = lambda a, b: jnp.matmul(  # noqa: E731
            a, b, preferred_element_type=jnp.float32)
        assert not _violations(
            lambda: (ok, (jnp.ones((8, 4), jnp.bfloat16),
                          jnp.ones((4, 8), jnp.bfloat16))), "dtype-policy")

    def test_const_bloat(self):
        big = np.ones((1 << 20,), np.float32)  # 4 MB baked closure

        def build():
            fn = lambda x: jnp.sum(x * jnp.asarray(big))  # noqa: E731
            return fn, (jnp.ones(1 << 20),)

        out = _violations(build, "const-bloat", max_const_bytes=1 << 20)
        assert out and "4.2 MB" in out[0].message
        # a bigger budget accepts the same program
        assert not _violations(build, "const-bloat",
                               max_const_bytes=8 << 20)

    def test_retrace_hazard_weak_arg(self):
        def build():
            return (lambda x, s: x * s), (jnp.ones(4), 0.5)

        out = _violations(build, "retrace-hazard")
        assert out and "weak-typed" in out[0].message

    def test_retrace_hazard_captured_scalar_const(self):
        scale = jnp.float32(3.0)  # device scalar baked into the closure

        def build():
            return (lambda x: x * scale), (jnp.ones(4),)

        out = _violations(build, "retrace-hazard")
        assert out and "captured scalar" in out[0].message

    def test_clean_program_no_violations(self):
        def build():
            fn = lambda x, s: jnp.sum(x * s)  # noqa: E731
            return fn, (jnp.ones(4), np.float32(0.5))

        assert _violations(build) == []


# ------------------------------------------------ trace-signature registry
class TestTraceSignatures:
    def test_weak_drift_detected(self):
        log = TraceSignatureLog()
        log.record("phi", (jnp.ones(8), 0.5))  # Python-scalar caller
        log.record("phi", (jnp.ones(8), np.float32(0.5)))  # array caller
        hazards = log.hazards()
        assert len(hazards) == 1 and hazards[0][0] == "phi"

    def test_legit_shape_change_is_not_drift(self):
        log = TraceSignatureLog()
        log.record("solve", (jnp.ones(8),))
        log.record("solve", (jnp.ones(16),))  # new shape = new program
        assert log.hazards() == []

    def test_identical_signatures_dedupe(self):
        log = TraceSignatureLog()
        a = log.record("f", (jnp.ones(4),))
        b = log.record("f", (jnp.zeros(4),))  # values differ, aval equal
        assert a == b and len(log.signatures("f")) == 1

    def test_weak_type_drift_predicate(self):
        a = trace_signature((jnp.ones(3), 1.0))
        b = trace_signature((jnp.ones(3), np.float32(1.0)))
        c = trace_signature((jnp.ones(3), np.float64(1.0)))
        assert weak_type_drift(a, b)
        assert not weak_type_drift(a, a)
        assert not weak_type_drift(b, c)  # dtype change: a real retrace

    def test_concurrent_record_is_safe(self):
        # Round-18 regression: _seen is mutated from serving threads while
        # hazards() iterates — must not lose entries or raise RuntimeError.
        import threading

        log = TraceSignatureLog()
        args = [(jnp.ones(4), 0.5), (jnp.ones(4), np.float32(0.5))]
        errs: list = []

        def pound(i: int) -> None:
            try:
                for k in range(200):
                    log.record(f"fn{(i + k) % 4}", args[k % 2])
                    log.hazards()
            except Exception as e:  # pragma: no cover - failure path
                errs.append(e)

        ts = [threading.Thread(target=pound, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert errs == []
        hazards = log.hazards()
        assert sorted(h[0] for h in hazards) == ["fn0", "fn1", "fn2", "fn3"]
        for name in ("fn0", "fn1", "fn2", "fn3"):
            assert len(log.signatures(name)) == 2
