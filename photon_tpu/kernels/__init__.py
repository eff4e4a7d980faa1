"""Pallas TPU kernels for the measured sparse soft spots — the machine-code
half of ROADMAP open item 4 ("spend the ledger's gap").

PR 8's attribution ledger and PERF.md rounds 11-12 measured exactly where
the blocked-ELL hot path leaves hardware on the table: the tail matvec's
concat + `row_pos` reassembly is an extra HBM round-trip of the (B,)
bucket outputs per X pass, the per-slot w-gather pays an HBM access
granule per ELL slot INCLUDING the 12.3% pow2 padding, and the
occurrence-bucket rmatvec re-reads the cotangent per bucket. This package
closes that loop with two fused Pallas kernels (`kernels/blocked_ell.py`):

- **blocked-ELL tail matvec** — gather + bf16-multiply/f32-accumulate
  einsum + row reassembly in ONE kernel: the tail-coefficient slice
  ``w[d_sel:n_prefix]`` (~2 MB of distinct tail columns at 10M-feature
  scale, the round-12 fact) lives VMEM-resident for the whole kernel, so
  per-slot gathers — padded slots included — are VMEM-local instead of
  HBM granules, and the bucket outputs never materialize in HBM (the XLA
  path writes the (B,) concat out and gathers it back in).
- **occurrence-bucket rmatvec** — every bucket's pre-sorted gather +
  einsum in one kernel over a single VMEM-resident cotangent read,
  emitting the concatenated tail-gradient block directly.

DISPATCH SEAM (`data/matrix.py::BlockedEllRows.{matvec,rmatvec}` route
through `tail_matvec` / `bucket_rmatvec` here):

- ``PHOTON_TPU_KERNELS`` env knob: ``on`` dispatches the kernels — they
  compile for the attached device or the run fails with the compiler's
  message; ``off`` forces the XLA path; ``auto`` (default) is the XLA
  path too, because the v5e's compiler refuses every kernel of this
  package today (arbitrary in-kernel table gathers: "Only 2D gather is
  supported" — tests/test_chip_compile.py pins each message, PERF.md
  records them). Pallas interpret mode is a TEST harness only
  (`interpreted`); no product path interprets a kernel.
- `OptimizerConfig.kernels` threads the same three-state knob through
  `models/training.py` and `optim/streamed.py` per solve (None =
  inherit the env/auto default).
- Kernels step aside per call — a stated trace-time rule (`route`) —
  when a layout has no tail or exceeds the VMEM budget
  (``PHOTON_TPU_KERNELS_VMEM``); interpret-mode parity with the XLA path
  is pinned by tests/test_kernels.py and the
  `blocked_ell_kernel_x_passes` contract.

Flipping the effective mode mid-process clears jit caches (the
`telemetry.taps` arming precedent): the dispatch branch is a trace-time
fact, not part of jit's cache key, so a cached program would otherwise
keep its old path. The seam itself never changes CALL signatures —
`KERNEL_SIGNATURES` records every dispatch and the registered no-retrace
contract refuses signature divergence between modes.

``python -m photon_tpu.kernels --selftest`` is the 9th umbrella
selfcheck suite (interpret parity matrix + dispatch invariance + the
registered contracts).
"""
from __future__ import annotations

import contextlib

from photon_tpu.analysis.rules import TraceSignatureLog
from photon_tpu.utils import env as env_knobs

from photon_tpu.kernels.blocked_ell import (  # noqa: F401
    bucket_rmatvec,
    bucket_rmatvec_tiled,
    kernel_feasible,
    tail_matvec,
    tail_matvec_tiled,
    tiled_feasible,
)

__all__ = [
    "ENV_KNOB", "ENV_VMEM", "ENV_TILE", "KERNEL_SIGNATURES", "mode",
    "active", "interpret", "interpreted", "vmem_budget", "tile_override",
    "scope",
    "route", "tail_matvec", "bucket_rmatvec", "tail_matvec_tiled",
    "bucket_rmatvec_tiled", "kernel_feasible", "tiled_feasible",
]

ENV_KNOB = "PHOTON_TPU_KERNELS"
ENV_VMEM = "PHOTON_TPU_KERNELS_VMEM"
ENV_TILE = "PHOTON_TPU_KERNELS_TILE"
_MODES = ("on", "off", "auto")

# Dispatch-signature registry: the seam records every kernel dispatch's
# argument signature here; the `blocked_ell_kernel_no_retrace` contract
# (kernels/blocked_ell.py) replays dispatches under both modes and
# refuses any divergence — mode flips must never change call signatures.
KERNEL_SIGNATURES = TraceSignatureLog()

# Override stack (innermost wins) pushed by `scope` — the config-field
# face of the knob, threaded per solve by models/training.py and
# optim/streamed.py.
_OVERRIDES: list[str] = []


def _canon(m) -> str:
    m = str(m).strip().lower()
    aliases = {"1": "on", "true": "on", "0": "off", "false": "off",
               "": "auto"}
    m = aliases.get(m, m)
    if m not in _MODES:
        raise ValueError(
            f"{ENV_KNOB}/OptimizerConfig.kernels must be one of {_MODES} "
            f"(or 0/1), got {m!r}")
    return m


def mode() -> str:
    """The requested mode: innermost `scope` override, else the
    ``PHOTON_TPU_KERNELS`` env knob, else ``auto``."""
    if _OVERRIDES:
        return _OVERRIDES[-1]
    return _canon(env_knobs.get_raw(ENV_KNOB, "auto"))


_INTERPRETED = False


def interpret() -> bool:
    """Whether kernels run via Pallas ``interpret=True``: only inside a
    test harness's `interpreted` block. Everywhere else a dispatched
    kernel compiles for the attached device — or the run fails with the
    compiler's message; no backend check turns interpretation on."""
    return _INTERPRETED


@contextlib.contextmanager
def interpreted():
    """TEST HARNESS ONLY (tests/conftest.py, the ``--selftest`` CLIs): run
    every Pallas kernel of the repo — this package's and `ops/fused.py`'s
    — in interpret mode for the duration, so CPU tests can pin kernel
    arithmetic against the XLA path. Clears jit caches on entry and exit
    (the flag is a trace-time fact, like `scope`)."""
    import jax

    global _INTERPRETED
    before, _INTERPRETED = _INTERPRETED, True
    if not before:
        jax.clear_caches()
    try:
        yield
    finally:
        _INTERPRETED = before
        if not before:
            jax.clear_caches()


def active() -> bool:
    """Whether the dispatch seam routes to the Pallas kernels right now:
    only under ``on``. ``auto`` does not route to a kernel the chip's
    compiler refuses, and today it refuses all of this package's (module
    docstring) — the default route is the XLA path that compiles."""
    return mode() == "on"


def vmem_budget() -> int | None:
    """Per-call VMEM byte budget for the single-fused-kernel form; a
    layout whose operands exceed it routes to the grid-tiled forms (see
    `route`). In interpret mode (tests) there is no VMEM, so the budget
    is unbounded unless ``PHOTON_TPU_KERNELS_VMEM`` pins one.

    A malformed knob raises ``ValueError`` naming it HERE, at the knob
    seam — not a bare ``int()`` parse error surfacing from the first
    kernel dispatch deep inside a jitted X pass."""
    raw = env_knobs.get_raw(ENV_VMEM)
    if raw is not None:
        try:
            budget = int(raw)
        except ValueError:
            raise ValueError(
                f"{ENV_VMEM} must be an integer byte budget, got "
                f"{raw!r}") from None
        if budget < 0:
            raise ValueError(
                f"{ENV_VMEM} must be >= 0 bytes, got {budget}")
        return budget
    return None if interpret() else 12 << 20


def tile_override() -> int | None:
    """The ``PHOTON_TPU_KERNELS_TILE`` row-tile override for the
    grid-tiled kernel forms (None = defer to the autotuner's cached
    winner). Validated here: a positive pow2 multiple of 8 — the f32
    sublane quantum — or a ValueError naming the knob."""
    raw = env_knobs.get_raw(ENV_TILE)
    if raw is None:
        return None
    try:
        tile = int(raw)
    except ValueError:
        raise ValueError(
            f"{ENV_TILE} must be an integer row tile, got {raw!r}"
        ) from None
    if tile < 8 or tile & (tile - 1):
        raise ValueError(
            f"{ENV_TILE} must be a pow2 >= 8 (sublane-aligned row "
            f"tile), got {tile}")
    return tile


def route(X, vec) -> str | None:
    """The dispatch ladder of the blocked-ELL seam, as ONE trace-time
    verdict: ``"fused"`` (single grid-free kernel, every operand
    VMEM-resident), ``"tiled"`` (grid-tiled form — the layout exceeds
    `vmem_budget` but a per-bucket row tile plus the resident vector
    still fits), or ``None`` (XLA path: seam inactive, no tail, or even
    one tile would not fit). Mode flips clear jit caches (`scope`), so
    the verdict is a safe trace-time branch."""
    if not active():
        return None
    if kernel_feasible(X, vec):
        return "fused"
    if tiled_feasible(X, vec):
        return "tiled"
    return None


@contextlib.contextmanager
def scope(m=None):
    """Push a mode override for the duration (None = no-op inherit).

    A push/pop that CHANGES the effective `active()` verdict clears jit
    caches: cached programs traced under the old mode would otherwise
    keep dispatching the old path (the flag is not part of jit's cache
    key — exactly the telemetry-tap arming semantics)."""
    if m is None:
        yield
        return
    import jax

    before = active()
    _OVERRIDES.append(_canon(m))
    inside = active()
    if inside != before:
        jax.clear_caches()
    try:
        yield
    finally:
        _OVERRIDES.pop()
        if active() != inside:
            jax.clear_caches()
