"""AOT scoring-program ladder: the serving tier's program plane.

Online scoring lives in a regime the training stack never sees: many
tiny batches, where a single retrace (~100 ms) or recompile (~seconds)
blows the p99 budget by orders of magnitude. The defense is STATIC
SHAPES ONLY: requests are padded into a pow2 batch-size ladder
(`data.matrix.next_pow2`), and each (model, bucket) pair is ONE program
— exported ahead of time through `utils/aot.py::AotStore` (keyed by
model tag + `LADDER_SCHEMA` + jax version) so a serving process
deserializes at startup (`warmup`) and steady state never traces.

Two enforcement layers make "never traces, never exits to host" law
rather than hope:

- registered `ContractSpec`s (bottom of this file) prove the per-request
  program has zero collectives, zero host callbacks/transfers, and no
  f64 anywhere (so no dot over f64) — checked by
  ``python -m photon_tpu.analysis`` and tier-1 on every PR;
- a live `analysis.TraceSignatureLog`: every dispatch records its
  argument signature, and `assert_no_retrace()` proves N requests across
  mixed sizes produced at most ``len(ladder)`` distinct signatures (one
  compiled program per bucket) with zero weak-type drift.

The scoring math is EXACTLY the offline driver's per-chunk program
(drivers/score.py → game/scoring.py): margin = offsets + Σ fixed matvec
+ Σ random-effect rowwise gather-dot, contributions summed in coordinate
order, optionally through the task's inverse link. Row padding never
changes per-row reductions and the coefficient gather is exact, so
dispatcher-batched scores are bit-identical to `run_scoring`'s — the
parity tests/test_serving.py pins.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import numpy as np

from photon_tpu import telemetry
from photon_tpu.analysis.rules import TraceSignatureLog
from photon_tpu.data.matrix import SparseRows, next_pow2, quantize_blocks
from photon_tpu.game.model import score_rows
from photon_tpu.ops.losses import mean_fn
from photon_tpu.serving.store import CoefficientStore

# The program-ladder calling-convention tag: rides the AotStore cache key
# (with the jax version), so redesigning the argument layout below bumps
# this string and invalidates stale exports instead of replaying them.
LADDER_SCHEMA = "serving-ladder-v1"


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How one feature shard's request rows batch: ``sparse_k=None`` →
    dense (B, d) blocks; else padded-COO (B, k) index/value pairs."""

    name: str
    d: int
    sparse_k: Optional[int] = None


class QuantizationRefused(RuntimeError):
    """A quantized rung's warmup accuracy gate breached its epsilon: the
    quantized ladder does NOT serve (mirroring `continual.SwapRefused` —
    a rung whose margins moved past the configured bound never reaches
    traffic). Carries the measured report for the operator."""

    def __init__(self, report: dict):
        super().__init__(
            f"quantized serving rung refused: probe margin max |Δ| "
            f"{report['max_abs_diff']:.6g} over {report['n_probes']} rows "
            f"exceeds epsilon {report['epsilon']:.6g} "
            f"(mode={report['mode']})")
        self.report = report


def _build_score_fn(coords: tuple, task, output_mean: bool,
                    quantize: Optional[str] = None):
    """The per-bucket scoring program, closed over STRUCTURE only (names,
    routing, task, quantization MODE); every array — including the
    coefficient blocks — is an argument, so a coefficient hot-swap reuses
    the same executable.

    coords: ((name, kind, feature_shard), ...) in the GameModel's
    coordinate order, kind ∈ {"fixed", "random"} — contributions sum in
    exactly this order, which is what keeps serving scores bit-identical
    to the offline driver's `score_game` sum.

    With ``quantize`` the coefficient arguments are the quantized forms
    (`data.matrix.quantize_blocks`): int8 blocks gather at 1 B/element
    and the row-wise dequant (``q·scale``) FUSES into the margin matvec /
    gather-dot inside this one jitted program — the f32 coefficients
    never materialize in HBM; bf16 blocks upcast in registers the same
    way. The cold-miss row dequantizes to exact zeros by construction
    (all-zero rows quantize at scale 1.0).
    """
    import jax.numpy as jnp

    from photon_tpu.data.matrix import matvec

    mean = mean_fn(task)

    def score(offsets, shards, ids, fixed_ws, re_cs):
        margin = offsets
        for name, kind, shard in coords:
            if kind == "fixed":
                wq = fixed_ws[name]
                if quantize == "int8":
                    q, s = wq
                    wq = q.astype(jnp.float32) * s
                elif quantize == "bf16":
                    wq = wq.astype(jnp.float32)
                margin = margin + matvec(shards[shard], wq)
            else:
                # (E+1, d) flat block: row E is the zero cold-miss row,
                # so the gather itself IS the graceful degradation.
                cq = re_cs[name]
                if quantize == "int8":
                    q, s = cq
                    rows = (q[ids[name]].astype(jnp.float32)
                            * s[ids[name]][:, None])
                elif quantize == "bf16":
                    rows = cq[ids[name]].astype(jnp.float32)
                else:
                    rows = cq[ids[name]]
                margin = margin + score_rows(shards[shard], rows)
        return mean(margin) if output_mean else margin

    return score


class ProgramLadder:
    """AOT-exported scoring executables at a pow2 batch-size ladder.

    One program per (model_tag, bucket); `score_padded` dispatches a
    full-bucket batch through the matching executable and records the
    call signature. With ``aot_dir`` set, programs replay from the
    `AotStore` (no tracing in a warm process); without it they are plain
    jit programs (one trace per bucket per process — still bounded by
    the ladder).

    Keep ``floor`` ≥ 8 (the default) when bit-parity with the offline
    driver matters: XLA CPU's matvec kernel takes a different
    K-accumulation path below 8 rows, so a 4-rung batch can drift ULPs
    against the driver's 4096-row chunk program; every rung ≥ 8 is
    measured row-stable against any larger batch (docs/SERVING.md)."""

    def __init__(self, store: CoefficientStore, *,
                 max_batch: int = 256, floor: int = 8,
                 sparse_k: Optional[dict] = None,
                 output_mean: bool = True,
                 aot_dir: Optional[str] = None,
                 model_tag: str = "model",
                 ladder: Optional[tuple] = None,
                 quantize: Optional[str] = None,
                 quant_epsilon: float = 0.05):
        import jax

        if quantize not in (None, "int8", "bf16"):
            raise ValueError(
                f"quantize must be None, 'int8' or 'bf16', got {quantize!r}")
        self.quantize = quantize
        self.quant_epsilon = float(quant_epsilon)
        self.quant_report: Optional[dict] = None
        self._qdev = None  # (f32-generation token, quantized device blocks)
        self._qlock = threading.Lock()
        self.store = store
        self.output_mean = bool(output_mean)
        self.model_tag = model_tag
        if ladder is None:
            floor = min(next_pow2(floor, 1), next_pow2(max_batch, 1))
            rungs, b = [], floor
            while b < max_batch:
                rungs.append(b)
                b *= 2
            rungs.append(next_pow2(max_batch, 1))
            ladder = tuple(rungs)
        self.ladder = tuple(sorted(set(int(b) for b in ladder)))
        if any(b & (b - 1) or b < 1 for b in self.ladder):
            raise ValueError(f"ladder must be pow2 rungs, got {self.ladder}")
        dims = store.shard_dims()
        sparse_k = dict(sparse_k or {})
        unknown = set(sparse_k) - set(dims)
        if unknown:
            raise ValueError(f"sparse_k names unknown shards: {unknown}")
        self.shard_specs = {
            s: ShardSpec(s, d, sparse_k.get(s)) for s, d in dims.items()}
        coords = tuple(
            (name, "fixed", store.fixed[name].feature_shard)
            if name in store.fixed
            else (name, "random", store.random[name].feature_shard)
            for name in store.order)
        self._fn = _build_score_fn(coords, store.task, self.output_mean,
                                   quantize=self.quantize)
        self._jit = jax.jit(self._fn)
        if self.quantize is not None:
            # the warmup accuracy gate scores MARGINS both ways (the link
            # function would compress honest deltas near saturation)
            self._gate_f32 = jax.jit(_build_score_fn(coords, store.task,
                                                     False))
            self._gate_quant = jax.jit(_build_score_fn(coords, store.task,
                                                       False,
                                                       quantize=self.quantize))
        self._aot = None
        if aot_dir is not None:
            from photon_tpu.utils.aot import AotStore

            self._aot = AotStore(aot_dir, schema=LADDER_SCHEMA)
        self.signature_log = TraceSignatureLog()

    # ------------------------------------------------------------ bucketing
    @property
    def max_batch(self) -> int:
        return self.ladder[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest ladder rung ≥ n (requests above the top rung split
        upstream — the dispatcher's max_batch is the top rung)."""
        if n > self.ladder[-1]:
            raise ValueError(f"batch of {n} exceeds ladder top "
                             f"{self.ladder[-1]}")
        for b in self.ladder:
            if b >= n:
                return b
        raise AssertionError  # unreachable: checked above

    # ------------------------------------------------------------- programs
    def _key(self, bucket: int) -> str:
        tag = (self.model_tag if self.quantize is None
               else f"{self.model_tag}:{self.quantize}")
        return f"serving/{tag}@B{bucket}"

    def _quant_blocks(self) -> tuple:
        """(fixed_ws, re_cs) in this ladder's quantized form, computed
        row-wise at store load (`data.matrix.quantize_blocks`) and cached
        per coefficient GENERATION: a `reload_coefficients` hot-swap
        swings `device_blocks()` to a new tuple, which invalidates this
        cache — the next dispatch re-quantizes the new model (same
        shapes, so the rung executables replay untouched)."""
        import jax

        token = self.store.device_blocks()  # ONE generation, atomically
        with self._qlock:
            if self._qdev is not None and self._qdev[0] is token:
                return self._qdev[1]
            fixed_q: dict = {}
            for n, blk in self.store.fixed.items():
                q, s = quantize_blocks(np.asarray(blk.weights, np.float32),
                                       self.quantize)
                fixed_q[n] = (jax.device_put(q) if s is None
                              else (jax.device_put(q), np.float32(s)))
            re_q: dict = {}
            for n, blk in self.store.random.items():
                q, s = quantize_blocks(
                    np.asarray(blk.coefficients, np.float32), self.quantize)
                re_q[n] = (jax.device_put(q) if s is None
                           else (jax.device_put(q), jax.device_put(s)))
            blocks = (fixed_q, re_q)
            self._qdev = (token, blocks)
            return blocks

    def _coefficient_args(self) -> tuple:
        return (self.store.device_blocks() if self.quantize is None
                else self._quant_blocks())

    def _quant_gate(self) -> dict:
        """The measured accuracy gate (warmup refuses on breach): margins
        of a deterministic probe batch — every entity cycled through,
        cold-miss row included, N(0,1) rows per shard — through the f32
        and quantized programs; the worst |Δ| must sit within
        ``quant_epsilon`` (the `continual.swap.parity_probe` discipline,
        applied to the quantization instead of a refresh)."""
        B = self.ladder[0]
        rng = np.random.default_rng(0)
        shards = {}
        for s, spec in self.shard_specs.items():
            if spec.sparse_k is None:
                shards[s] = rng.normal(size=(B, spec.d)).astype(np.float32)
            else:
                shards[s] = SparseRows(
                    rng.integers(0, spec.d, size=(B, spec.sparse_k)).astype(
                        np.int32),
                    rng.normal(size=(B, spec.sparse_k)).astype(np.float32),
                    spec.d)
        ids = {name: (np.arange(B, dtype=np.int64)
                      % (self.store.n_entities(name) + 1)).astype(np.int32)
               for name in self.store.random}
        offsets = np.zeros(B, np.float32)
        fixed_ws, re_cs = self.store.device_blocks()
        m32 = np.asarray(self._gate_f32(offsets, shards, ids, fixed_ws,
                                        re_cs), np.float64)
        qf, qr = self._quant_blocks()
        mq = np.asarray(self._gate_quant(offsets, shards, ids, qf, qr),
                        np.float64)
        report = {"mode": self.quantize, "n_probes": int(B),
                  "max_abs_diff": float(np.max(np.abs(m32 - mq))),
                  "epsilon": self.quant_epsilon}
        self.quant_report = report
        return report

    def example_args(self, bucket: int) -> tuple:
        """Zero-filled arguments at one rung's exact signature (warmup +
        contract tracing; zeros are fine — programs are shape facts)."""
        B = int(bucket)
        shards = {}
        for s, spec in self.shard_specs.items():
            if spec.sparse_k is None:
                shards[s] = np.zeros((B, spec.d), np.float32)
            else:
                shards[s] = SparseRows(
                    np.zeros((B, spec.sparse_k), np.int32),
                    np.zeros((B, spec.sparse_k), np.float32), spec.d)
        ids = {name: np.full(B, self.store.n_entities(name), np.int32)
               for name in self.store.random}
        fixed_ws, re_cs = self._coefficient_args()
        return (np.zeros(B, np.float32), shards, ids, fixed_ws, re_cs)

    def score_padded(self, offsets, shards: dict, ids: dict):
        """Dispatch one full-bucket batch (already padded to a rung by
        the dispatcher). Returns the device array WITHOUT blocking — the
        retire side device_gets asynchronously."""
        B = int(np.asarray(offsets).shape[0])
        if B not in self.ladder:
            raise ValueError(f"padded batch of {B} is not a ladder rung "
                             f"{self.ladder}")
        fixed_ws, re_cs = self._coefficient_args()
        args = (offsets, shards, ids, fixed_ws, re_cs)
        self.signature_log.record("serving.score", args)
        if self._aot is not None:
            return self._aot.call(self._key(B), self._fn, *args)
        return self._jit(*args)

    def warmup(self) -> int:
        """Pre-load/compile every rung's program (serving startup): with
        an AotStore, `AotStore.warmup` replays or exports each entry; a
        jit-only ladder runs each rung once. Returns rungs warmed.

        A QUANTIZED ladder gates first: the measured probe margin delta
        vs the f32 program must sit within ``quant_epsilon``, else
        `QuantizationRefused` (counted on ``serving.quant_refusals``) —
        an unacceptably lossy quantization never warms, never serves."""
        if self.quantize is not None:
            report = self._quant_gate()
            if report["max_abs_diff"] > report["epsilon"]:
                telemetry.count("serving.quant_refusals")
                raise QuantizationRefused(report)
        entries = [(self._key(B), self._fn, self.example_args(B))
                   for B in self.ladder]
        if self._aot is not None:
            return self._aot.warmup(entries)
        for _, _, args in entries:
            self._jit(*args)
        return len(entries)

    # ------------------------------------------------------------ assertions
    def assert_no_retrace(self) -> int:
        """Prove steady-state serving never retraced: every dispatch so
        far used one of at most ``len(ladder)`` argument signatures (one
        executable per rung) and no signature pair drifts only by
        weak_type. Returns the distinct-signature count."""
        sigs = self.signature_log.signatures("serving.score")
        if len(sigs) > len(self.ladder):
            raise AssertionError(
                f"{len(sigs)} distinct scoring signatures exceed the "
                f"{len(self.ladder)}-rung ladder: serving retraced")
        hazards = self.signature_log.hazards()
        if hazards:
            raise AssertionError(
                f"weak-type signature drift in serving dispatch: {hazards}")
        return len(sigs)


# ----------------------------------------------------------------- contracts
# The per-request scoring program, pinned as law: ZERO collectives (a
# request touches one chip), ZERO host callbacks/transfers (the dispatcher
# pipeline only overlaps if the program never exits to host), no f64
# anywhere — so no dot over f64 — and nothing baked in (coefficients are
# ARGUMENTS; a baked block would both bloat every rung's executable and
# force a retrace on model push).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402


def _tiny_store() -> CoefficientStore:
    """Example-store builder shared by the serving contracts: one dense
    fixed shard + one sparse random-effect shard, zeros throughout
    (contracts are shape facts). Constructed directly — no jit runs."""
    from photon_tpu.data.index_map import IndexMap
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.serving.store import FixedBlock, RandomBlock

    d_f, d_r, E = 12, 6, 5
    directory = IndexMap({f"e{i}": i for i in range(E)}, frozen=True)
    return CoefficientStore(
        TaskType.LOGISTIC_REGRESSION, ("fixed", "perEntity"),
        {"fixed": FixedBlock("global", np.zeros(d_f, np.float32))},
        {"perEntity": RandomBlock("member", "memberId",
                                  np.zeros((E + 1, d_r), np.float32),
                                  directory)})


@register_contract(
    name="serving_request_program",
    description="one serving-ladder rung end to end: dense fixed matvec + "
                "sparse random-effect gather-dot + inverse link, "
                "coefficients as arguments — no collectives, no host "
                "exits, no f64, nothing baked in",
    collectives={}, tags=("serving", "game"))
def _contract_serving_request():
    ladder = ProgramLadder(_tiny_store(), ladder=(8,), sparse_k={"member": 3},
                           output_mean=True)
    args = ladder.example_args(8)
    return ladder._fn, args


@register_contract(
    name="serving_quantized_rung_invariance",
    description="one QUANTIZED serving rung (int8 blocks + row-wise "
                "scales as arguments, dequant fused into the margin "
                "matvec): the same zero-collective / zero-host-exit / "
                "no-f64 law as the f32 rungs, and program INVARIANCE — "
                "the builder swaps coefficient values (a hot-swap's "
                "re-quantization) and raises if the rung's dispatch "
                "signature moves, so a model push never retraces a "
                "quantized ladder",
    collectives={}, tags=("serving",))
def _contract_serving_quantized_rung():
    ladder = ProgramLadder(_tiny_store(), ladder=(8,),
                           sparse_k={"member": 3}, output_mean=True,
                           quantize="int8")
    args = ladder.example_args(8)
    log = TraceSignatureLog()
    log.record("serving.quant_rung", args)
    # a hot-swap re-quantizes NEW values into the SAME shapes: the rung
    # signature must not move (same-structure store, fresh arrays)
    ladder.store.reload_coefficients(_tiny_store())
    log.record("serving.quant_rung", ladder.example_args(8))
    sigs = log.signatures("serving.quant_rung")
    if len(sigs) != 1:
        raise AssertionError(
            f"quantized rung dispatch drifted across a coefficient "
            f"reload: {len(sigs)} signatures (expected 1)")
    if log.hazards():
        raise AssertionError(
            f"quantized rung weak-type drift: {log.hazards()}")
    return ladder._fn, args


@register_contract(
    name="serving_request_margin",
    description="the margin-only serving rung (output_mean=False, dense "
                "random-effect shard): the raw-score head obeys the same "
                "zero-collective / zero-host-exit / no-f64 law",
    collectives={}, tags=("serving",))
def _contract_serving_margin():
    ladder = ProgramLadder(_tiny_store(), ladder=(4,), output_mean=False)
    args = ladder.example_args(4)
    return ladder._fn, args
