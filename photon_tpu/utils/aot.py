"""Ahead-of-time program export (jax.export): skip re-TRACING across
processes.

The persistent XLA compilation cache (utils/compile_cache.py) removes
re-compilation across processes, but a fresh process still pays jax
tracing + lowering for every program — a residual no compilation cache
can touch, and one the reference's long-lived JVM never re-pays.
``jax.export`` serializes the traced StableHLO itself, so a later process
deserializes and goes straight to (persistently cached) compilation.

The replay removes only the trace+lowering share (benches/aot_glm.py is
the A/B; not measured on the current chip — PERF.md). The utility earns
its keep where traces are the bottleneck: many programs, many shapes.

Pieces:
- ``export_program(fn, *args, platforms=None) -> bytes`` — trace + lower
  ``fn`` at ``args``'s shapes/dtypes and serialize. ``fn`` may be jitted
  or plain (it is jitted if needed). ``platforms`` (e.g. ``("tpu",
  "cpu")``) widens the export beyond the current default backend.
- ``load_program(data)`` — deserialize to a callable. Shape/dtype
  specialized: calling with different avals raises.
- ``AotStore(cache_dir)`` — a keyed on-disk store.
  ``store.call(key, fn, *args)`` replays a previous export when the key
  AND the arguments' avals match, else exports (and persists) fresh.
  File identity also covers the running jax version and an optional
  caller ``schema`` tag (a jax upgrade or a program-layout redesign
  re-exports instead of failing at replay), and ``store.warmup(entries)``
  pre-loads + compiles a list of entries — serving startup runs the whole
  program ladder through it before the first live request.

Scope: single-controller programs (anything photon-tpu jits on one
device, including everything ``train_glm``/``train_glm_grid``/
``score_game`` run there). Mesh/shard_map programs are exportable too,
but calling a deserialized one requires reconstructing the SAME mesh
layout first — use ``export_program``/``load_program`` directly and
own the mesh lifecycle in that case rather than going through the
store.
"""
from __future__ import annotations

import hashlib
import os
from typing import Callable, Optional, Sequence

import jax

__all__ = ["export_program", "load_program", "AotStore"]

_registered = False


def _serialize_auxdata(aux) -> bytes:
    """Auxdata (the static/meta fields of our register_dataclass pytrees)
    as JSON: the payload is plain ints/strings/bools/enums/tuples, so a
    safe serializer covers it — pickle.loads on a shared or
    attacker-writable cache dir would be an arbitrary-code-execution
    hole, and nothing enforced the single-process trust domain the old
    comment assumed. Tuples and enums round-trip through tagged dicts
    (tuple-ness matters: auxdata equality is pytree equality)."""
    import enum
    import json

    def enc(v):
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        if isinstance(v, enum.Enum):
            t = type(v)
            return {"__enum__": [t.__module__, t.__qualname__, v.name]}
        if isinstance(v, tuple):
            return {"__tuple__": [enc(x) for x in v]}
        if isinstance(v, list):
            return [enc(x) for x in v]
        raise TypeError(
            f"unsupported auxdata type {type(v).__name__!r}: extend "
            "_serialize_auxdata rather than falling back to pickle")

    return json.dumps(enc(aux)).encode()


def _deserialize_auxdata(data: bytes):
    import enum
    import importlib
    import json

    def dec(v):
        if isinstance(v, dict):
            if "__enum__" in v:
                mod, qual, name = v["__enum__"]
                obj = importlib.import_module(mod)
                for part in qual.split("."):
                    obj = getattr(obj, part)
                if not (isinstance(obj, type) and issubclass(obj, enum.Enum)):
                    raise ValueError(
                        f"auxdata names non-enum {mod}.{qual}")
                return obj[name]
            if "__tuple__" in v:
                return tuple(dec(x) for x in v["__tuple__"])
            raise ValueError(f"unrecognized auxdata tag {sorted(v)}")
        if isinstance(v, list):
            return [dec(x) for x in v]
        return v

    return dec(json.loads(data.decode()))


def _register_serializations() -> None:
    """Register photon-tpu's pytree node types with jax.export so they can
    appear in an exported program's calling convention. Auxdata rides the
    JSON codec above (no code execution on load)."""
    global _registered
    if _registered:
        return
    from jax import export as jexport

    from photon_tpu.data import matrix as _mx
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.ops.objective import Objective
    from photon_tpu.optim.tracker import OptResult

    def reg(cls):
        name = f"photon_tpu.{cls.__module__}.{cls.__name__}"
        try:
            jexport.register_pytree_node_serialization(
                cls, serialized_name=name,
                serialize_auxdata=_serialize_auxdata,
                deserialize_auxdata=_deserialize_auxdata)
        except ValueError:
            pass  # already registered (e.g. two stores in one process)

    def reg_nt(cls):
        try:
            jexport.register_namedtuple_serialization(
                cls,
                serialized_name=f"photon_tpu.{cls.__module__}.{cls.__name__}")
        except ValueError:
            pass

    for cls in (_mx.SparseRows, _mx.HybridRows, _mx.ShardedHybridRows,
                _mx.PermutedHybridRows, _mx.ShardedPermutedHybridRows,
                _mx.BlockedEllRows, _mx.ShardedBlockedEllRows,
                Objective, Coefficients, GeneralizedLinearModel):
        reg(cls)
    for cls in (GLMBatch, OptResult):
        reg_nt(cls)
    # photon: unguarded(idempotent fast-path memo — a duplicate concurrent registration is absorbed by the except-ValueError pass above; worst case is one redundant pass through reg())
    _registered = True


def _ensure_jitted(fn: Callable) -> Callable:
    # jax.export requires a jitted callable; wrapping an already-jitted
    # function in jax.jit again is a no-op layer, so just branch.
    if hasattr(fn, "lower"):  # jitted functions expose .lower
        return fn
    return jax.jit(fn)


def export_program(fn: Callable, *args,
                   platforms: Optional[Sequence[str]] = None) -> bytes:
    """Serialize ``fn`` traced at ``args``'s shapes/dtypes to bytes."""
    from jax import export as jexport

    _register_serializations()
    kwargs = {}
    if platforms is not None:
        kwargs["platforms"] = tuple(platforms)
    exp = jexport.export(_ensure_jitted(fn), **kwargs)(*args)
    return exp.serialize()


def load_program(data: bytes) -> Callable:
    """Deserialize an ``export_program`` blob to a callable."""
    from jax import export as jexport

    _register_serializations()
    return jexport.deserialize(data).call


def _avals_fingerprint(args) -> str:
    """Hash of the argument pytree's structure + leaf shapes/dtypes (the
    specialization key of an export)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in leaves:
        x = jax.numpy.asarray(leaf) if not hasattr(leaf, "shape") else leaf
        h.update(f"{tuple(x.shape)}:{x.dtype}".encode())
    return h.hexdigest()[:16]


class AotStore:
    """On-disk keyed store of exported programs.

    >>> store = AotStore("/path/to/aot")
    >>> out = store.call("train_glm@2Mx10M", fn, *args)

    First call per (key, avals): traces, exports, persists, runs.
    Later processes: deserializes (no tracing) and runs — compilation
    itself is then served by the persistent XLA cache when enabled.
    """

    def __init__(self, cache_dir: str,
                 platforms: Optional[Sequence[str]] = None,
                 schema: str = ""):
        self.cache_dir = cache_dir
        self.platforms = platforms
        # Caller-owned layout tag (e.g. the serving program-ladder schema):
        # bumping it invalidates every export whose calling convention the
        # caller redesigned, without touching unrelated keys.
        self.schema = schema
        self._loaded: dict = {}
        os.makedirs(cache_dir, exist_ok=True)

    def _path(self, key: str, fp: str) -> str:
        # The export's platform set is part of its calling convention, so
        # it is part of the file identity (a store populated for "cpu"
        # must not shadow one for ("tpu", "cpu")). The jax version is too:
        # jax.export blobs carry a serialization version a different jax
        # may refuse to (or worse, subtly mis-) replay — a jax upgrade
        # must MISS and re-export, not fail at replay time. Same for the
        # caller's schema tag.
        plat = ",".join(self.platforms) if self.platforms else "default"
        ident = f"{key}|{plat}|jax={jax.__version__}|schema={self.schema}"
        safe = hashlib.sha256(ident.encode()).hexdigest()[:16]
        return os.path.join(self.cache_dir, f"{safe}-{fp}.jaxexp")

    def warmup(self, entries) -> int:
        """Pre-trace/compile a list of ``(key, fn, example_args)`` entries.

        Each entry replays (or exports fresh) and RUNS once on its example
        arguments — zeros of the right shape are fine — so a serving
        process pays every deserialize + compile at startup instead of on
        the first live request of each shape. Returns the number warmed."""
        n = 0
        for key, fn, args in entries:
            self.call(key, fn, *args)
            n += 1
        return n

    def call(self, key: str, fn: Callable, *args):
        """Run ``fn(*args)``, replaying a stored export when available.

        ``key`` must capture everything that changes the PROGRAM beyond
        the arguments' shapes/dtypes — closure-captured static config,
        solver version — because the store cannot see inside ``fn``; a
        stale key replays the old program. Argument avals and the
        store's platform set are fingerprinted automatically; a replay
        whose stored platform no longer matches the running backend
        falls back to a fresh export instead of raising."""
        fp = _avals_fingerprint(args)
        path = self._path(key, fp)
        cached = self._loaded.get(path)
        if cached is None and os.path.exists(path):
            with open(path, "rb") as f:
                cached = load_program(f.read())
            # photon: unguarded(idempotent memo of an immutable loaded program — concurrent loaders store equivalent values and the GIL keeps the dict slot whole; locking here would hold a lock across deserialization)
            self._loaded[path] = cached
        if cached is not None:
            try:
                return cached(*args)
            except ValueError as e:
                # jax.export's call-time platform check raises ValueError
                # ("Function '<f>' was exported for platforms '<p>' but it
                # is used on '<q>'") when the file was exported for a
                # different backend (e.g. a store populated on a CPU dev
                # box now read on a TPU VM). Self-heal by re-exporting for
                # the current platform — but ONLY for that error: a
                # genuine ValueError from the replayed program must
                # surface, not be swallowed into a silent re-export that
                # re-runs the same failure.
                msg = str(e)
                if not ("was exported for" in msg and "platform" in msg):
                    raise
                # photon: unguarded(eviction of a wrong-platform entry is idempotent — a racing evictor just finds the slot already empty)
                self._loaded.pop(path, None)
        data = export_program(fn, *args, platforms=self.platforms)
        # temp + fsync + rename (checkpoint.store.commit_bytes): atomic
        # against concurrent processes AND durable against a kill
        # mid-write — a preemption can no longer leave a truncated export
        # that fails (or worse, half-replays) at the next load.
        from photon_tpu.checkpoint.store import commit_bytes

        commit_bytes(path, data)
        run = load_program(data)
        # photon: unguarded(idempotent memo — concurrent exporters produce the same program and commit_bytes keeps the file atomic; last store wins with an equivalent value)
        self._loaded[path] = run
        return run(*args)
