"""Noise-aware bench regression sentinel: the automated gate over the
repo's BENCH_r0*.json trajectory.

The bench harness appends one JSON round per PR (``{"n", "cmd", "rc",
"tail", "parsed": {"metric", "value", "legs": {...}}}``). Each leg is a
best-of-REPS wall-clock-derived rate on a shared host, noisy from run to
run — so a naive "slower than last round" gate would cry wolf weekly. This module fits a robust location/
scale per leg (median + MAD over the history) and flags a candidate only
when it lands beyond ``z_threshold`` robust z-scores on the leg's BAD
side (lower for throughput/QPS legs, higher for latency/overhead legs).

Noise-awareness, concretely:

- scale = max(1.4826·MAD, ``REL_FLOOR``·|median|, eps): with 3–6 history
  points the MAD routinely collapses to ~0 on a stable leg, which would
  make ANY drift infinitely significant — the relative floor keeps the
  gate honest about the bench's own documented run-to-run jitter.
- a leg with fewer than ``min_history`` prior observations is ADMITTED
  with status ``"new"`` (a brand-new bench leg must not trip the gate
  that merges it), and a missing/empty history degrades the whole gate
  to warn-only (``"no-history"``).
- improvements never trip anything; they report ``"ok"`` with their
  (negative-bad-direction) z so the JSON line still records the movement.
- a leg's history series is SINGLE-ENVIRONMENT: each round may carry a
  measured host fingerprint (``parsed["env"]``, ``host_env()``), and a
  candidate gates only against rounds with a MATCHING fingerprint
  (``same_env``). Rounds measured on different machines are different
  experiments — the r06 TPU→CPU break already excluded the TPU legs by
  hand; r10 (a container-host swap: ~2× single-core speed, ~5× disk)
  made the policy automatic. At a break, gating strength rebuilds over
  ``MIN_HISTORY`` rounds exactly as it did at r06. Legacy rounds with
  no fingerprint form their own series (env ``None``).

Deliberately jax-free and numpy-light: ``bench.py --gate`` runs this
BEFORE the heavyweight bench imports, so gating a PR costs milliseconds,
not a benchmark run. `photon_tpu.profiling.__main__ --report` embeds the
same verdicts beside the attribution ledger.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import re
from typing import Iterable, Optional

__all__ = [
    "DEFAULT_Z", "MIN_HISTORY", "REL_FLOOR", "SCHEMA_VERSION",
    "LegVerdict", "leg_values", "lower_is_better", "host_env",
    "env_key", "load_history", "same_env", "fit_legs", "gate",
    "verdict_lines", "gate_main",
]

# Robust z beyond which a bad-direction move is a regression. 3.5 is the
# classic modified-z outlier cut; with the REL_FLOOR below it means
# "worse than the leg's median by > max(3.5 MADs, ~35%)".
DEFAULT_Z = 3.5

# Legs observed in fewer prior rounds than this are admitted as "new".
MIN_HISTORY = 3

# Relative scale floor (fraction of |median|): the bench's own documented
# best-of drift; keeps a MAD-collapsed leg from flagging pure jitter.
REL_FLOOR = 0.10

# bench.py JSON-line schema: 1 = the historical implicit shape, 2 adds
# {"schema", "gate"} (this module's verdicts embedded per leg).
SCHEMA_VERSION = 2

# Legs where LOWER is better (latency, overhead, waste, shed); everything
# else is a rate/score where higher is better. "shed": the serving_slo
# overload legs — a rising shed percentage at the SAME offered rate means
# the tier got slower, a real regression (the shed-vs-queue TRADE is
# by design; its cost moving is not). "maxdiff": the quantized rungs'
# measured probe-margin delta — a louder quantization is a quality
# regression even when QPS holds. "dcn_bytes": the multi-process
# spine's priced per-eval wire bill (round 17) — a grown psum payload
# means something besides the gradient started riding DCN.
_LOWER_BETTER_PATTERNS = ("_ms", "overhead_pct", "pad_waste", "latency",
                         "stall", "shed", "maxdiff", "dcn_bytes",
                         "staleness")

# Config-ish / count legs that are not performance quantities: a changed
# topology, cadence, or layout split must not read as a "regression".
# (_frac / _width_buckets: the round-12 sparse hot/tail-split facts — a
# retuned d_dense would move them by design; pad_waste stays GATED,
# lower-better, because growing pow2 padding is a real cost. slo_target:
# the serving SLO bar is a chosen config, not a measurement.)
_EXCLUDE_PATTERNS = ("_n_chips", "n_requests", "snapshots", "cadence",
                     "_vs_baseline", "_frac", "_width_buckets",
                     "slo_target", "_n_configs", "_n_processes")


def lower_is_better(leg: str) -> bool:
    return any(p in leg for p in _LOWER_BETTER_PATTERNS)


def _gated(leg: str) -> bool:
    return not any(p in leg for p in _EXCLUDE_PATTERNS)


def leg_values(parsed: Optional[dict]) -> dict[str, float]:
    """Flatten one round's ``parsed`` object into {leg: value}. The
    headline ``value`` rides under its ``metric`` name so it is gated
    like any other leg; excluded/config legs and non-numerics drop."""
    if not parsed:
        return {}
    out: dict[str, float] = {}
    metric = parsed.get("metric")
    value = parsed.get("value")
    if metric and isinstance(value, (int, float)):
        out[str(metric)] = float(value)
    for leg, v in (parsed.get("legs") or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and _gated(leg):
            out[str(leg)] = float(v)
    return out


def host_env() -> str:
    """This machine's bench-comparability fingerprint: CPU model + the
    visible core count. Two rounds are comparable iff their fingerprints
    are EQUAL — rates move with the core, and the gate must not read a
    container-host swap as a code regression (nor absorb one into the
    MAD and then miss a real one). Disk class is deliberately absent:
    it has no discrete label to key on; I/O-bound legs on a swapped
    disk still need the fingerprint break above to reset their series."""
    model = ""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{model or 'unknown-cpu'}/nproc={os.cpu_count()}"


def env_key(parsed: Optional[dict]) -> Optional[str]:
    """One round's recorded host fingerprint (``None`` for the legacy
    rounds that predate fingerprinting — their own series)."""
    if not parsed:
        return None
    env = parsed.get("env")
    return env if isinstance(env, str) else None


def same_env(history: Iterable[tuple], env: Optional[str]) -> list[tuple]:
    """The single-environment slice of the history: rounds whose
    fingerprint matches ``env``. Bare ``(name, legs)`` pairs (tests,
    pre-fingerprint callers) count as env ``None``."""
    return [h for h in history
            if (h[2] if len(h) > 2 else None) == env]


def _round_key(path: str) -> tuple:
    m = re.search(r"_r(\d+)", os.path.basename(path))
    return (int(m.group(1)) if m else -1, os.path.basename(path))


def load_history(bench_dir: str, pattern: str = "BENCH_r*.json"
                 ) -> list[tuple[str, dict, Optional[str]]]:
    """[(round_name, {leg: value}, env_fingerprint)] in round order.
    Rounds whose file is unreadable or whose ``parsed`` is null
    contribute nothing (the r01 seed round predates the JSON-line
    protocol); rounds that predate fingerprinting carry env ``None``."""
    out = []
    for path in sorted(glob.glob(os.path.join(bench_dir, pattern)),
                       key=_round_key):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = doc.get("parsed")
        legs = leg_values(parsed)
        if legs:
            out.append((os.path.basename(path), legs, env_key(parsed)))
    return out


def fit_legs(history: Iterable[tuple]) -> dict[str, dict]:
    """Per-leg robust location/scale over the history (``(name, legs)``
    pairs or ``(name, legs, env)`` triples — filter with ``same_env``
    FIRST; the fit itself is fingerprint-blind):
    {leg: {median, mad, scale, n}}."""
    series: dict[str, list[float]] = {}
    for item in history:
        for leg, v in item[1].items():
            series.setdefault(leg, []).append(v)
    fits = {}
    for leg, vals in series.items():
        vals = sorted(vals)
        n = len(vals)
        med = (vals[n // 2] if n % 2 else
               0.5 * (vals[n // 2 - 1] + vals[n // 2]))
        devs = sorted(abs(v - med) for v in vals)
        mad = (devs[n // 2] if n % 2 else
               0.5 * (devs[n // 2 - 1] + devs[n // 2]))
        scale = max(1.4826 * mad, REL_FLOOR * abs(med), 1e-12)
        fits[leg] = {"median": med, "mad": mad, "scale": scale, "n": n}
    return fits


@dataclasses.dataclass
class LegVerdict:
    """One leg's gate outcome. ``status``: "ok" | "regressed" | "new"
    (short/absent history — admitted) | "no-history" (whole gate is
    warn-only). ``z`` is signed so that POSITIVE means worse (the bad
    direction), regardless of the leg's orientation."""

    leg: str
    status: str
    value: float
    z: Optional[float] = None
    median: Optional[float] = None
    n_history: int = 0
    lower_better: bool = False

    @property
    def line(self) -> str:
        """The one-line verdict embedded in the bench JSON output."""
        if self.status in ("new", "no-history"):
            return (f"{self.status} ({self.n_history} prior round(s); "
                    f"admitted without gating)")
        arrow = "lower-better" if self.lower_better else "higher-better"
        return (f"{self.status} (z={self.z:+.2f} vs median "
                f"{self.median:.6g} over {self.n_history} round(s), "
                f"{arrow})")

    def to_json(self) -> dict:
        out = {"status": self.status, "value": self.value,
               "n_history": self.n_history, "line": self.line}
        if self.z is not None:
            out["z"] = round(self.z, 3)
        if self.median is not None:
            out["median"] = self.median
        return out


def gate(candidate: dict[str, float],
         history: Iterable[tuple],
         z_threshold: float = DEFAULT_Z,
         min_history: int = MIN_HISTORY) -> dict[str, LegVerdict]:
    """Judge one round's legs against the history. Regression == the
    signed-bad-direction z exceeds ``z_threshold``; short-history legs
    admit as "new"; an empty history marks everything "no-history".
    The statistics are fingerprint-blind — pass the candidate's
    ``same_env`` slice, not the raw trajectory."""
    history = list(history)
    fits = fit_legs(history)
    verdicts: dict[str, LegVerdict] = {}
    for leg, value in sorted(candidate.items()):
        if not _gated(leg):
            continue
        low = lower_is_better(leg)
        if not history:
            verdicts[leg] = LegVerdict(leg, "no-history", value,
                                       lower_better=low)
            continue
        fit = fits.get(leg)
        if fit is None or fit["n"] < min_history:
            verdicts[leg] = LegVerdict(
                leg, "new", value, n_history=0 if fit is None else fit["n"],
                lower_better=low)
            continue
        z = (value - fit["median"]) / fit["scale"]
        bad_z = z if low else -z  # positive == worse, always
        ok = not (math.isfinite(bad_z) and bad_z > z_threshold)
        verdicts[leg] = LegVerdict(
            leg, "ok" if ok else "regressed", value, z=bad_z,
            median=fit["median"], n_history=fit["n"], lower_better=low)
    return verdicts


def verdict_lines(verdicts: dict[str, LegVerdict]) -> list[str]:
    return [f"{leg}: {v.line}" for leg, v in sorted(verdicts.items())]


def _load_candidate(path: str) -> tuple[dict[str, float], Optional[str]]:
    """(legs, env_fingerprint) for a candidate round from a file holding
    either a BENCH_r0*.json wrapper or a bare bench JSON line."""
    with open(path) as fh:
        doc = json.load(fh)
    parsed = doc.get("parsed") if "parsed" in doc else doc
    return leg_values(parsed), env_key(parsed)


def gate_main(argv: list[str], bench_dir: Optional[str] = None) -> int:
    """The ``bench.py --gate`` entry: candidate = --gate-candidate FILE,
    or the LATEST history round (gated against the earlier ones, sliced
    to the candidate's host fingerprint). Prints one verdict line per
    leg plus a summary JSON line; exit 1 iff any leg regressed."""
    def _flag(name: str, default=None):
        return (argv[argv.index(name) + 1] if name in argv else default)

    bench_dir = _flag("--gate-dir", bench_dir or os.getcwd())
    z = float(_flag("--gate-z", DEFAULT_Z))
    cand_path = _flag("--gate-candidate")
    history = load_history(bench_dir)
    if cand_path is not None:
        candidate, cand_env = _load_candidate(cand_path)
    elif history:
        _, candidate, cand_env = history[-1]
        history = history[:-1]
    else:
        candidate, cand_env = {}, None
    history = same_env(history, cand_env)
    verdicts = gate(candidate, history, z_threshold=z)
    for line in verdict_lines(verdicts):
        print(line)
    regressed = sorted(leg for leg, v in verdicts.items()
                       if v.status == "regressed")
    print(json.dumps({
        "metric": "bench_gate", "schema": SCHEMA_VERSION,
        "ok": not regressed, "z_threshold": z, "env": cand_env,
        "n_history_rounds": len(history), "n_legs": len(verdicts),
        "regressed": regressed,
        "verdicts": {leg: v.to_json() for leg, v in verdicts.items()},
    }))
    return 1 if regressed else 0
