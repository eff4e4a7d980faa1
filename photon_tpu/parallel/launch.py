"""Single-box multi-process launcher: the test substrate for the
multi-host data-parallel spine (ROADMAP item 2).

``launch(target, n_processes)`` spawns N fresh OS processes
(spawn-context — no forked XLA runtime state, lint rule 8), forms a
jax.distributed cluster of them over a localhost coordinator, and runs
``target(ctx)`` in every process. Device counts are pinned so EVERY
process count presents the same global mesh: with ``total_devices=8``
(the repo's virtual-mesh convention), 1 process sees 8 local devices,
2 processes see 4 each, 4 see 2 each — the same 8 global device slots,
so `mesh.shard_rows` / `local_row_slots` arithmetic and the hierarchical
psum are EXACTLY the programs a real pod runs, and (via the gloo
collectives `initialize_distributed` pins on CPU) the results are
bit-identical across process counts.

CPU ONLY, by construction: every child is pinned to the CPU backend in
code (`_child_main`), because a chip belongs to one process at a time —
the parent, or the first child, would hold it and the rest would fail or
hang. So this launcher checks the multi-process PROGRAM (cluster
formation, row slots, the hierarchical psum) and can never measure a
chip: `bench.py`'s multihost leg that rides it is a parity check, not a
device number, and `chip_smoke.py` leaves it out (its four-chip path is
one process driving four devices).

The child protocol, in order, before any jax import can touch a backend:

1. ``JAX_PLATFORMS`` / ``XLA_FLAGS`` (device count) exported;
2. `parallel.mesh.initialize_distributed(coordinator, N, rank)` — which
   pins gloo CPU collectives and forms the cluster;
3. ``target(LaunchContext)`` runs; its return value (picklable) rides a
   Pipe back to the parent.

Failure story: a child that raises ships the formatted traceback to the
parent, which kills + joins EVERY child before raising
:class:`ChildFailure` — zero lost/hung children by construction (the
``finally`` path terminates stragglers; `join` is unconditional). A
sandbox that blocks even localhost gRPC surfaces as
:class:`ClusterUnavailable`, which callers (tests, the bench leg) treat
as an environment skip, never a silent pass.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
import socket
import traceback
from typing import Callable, Optional, Sequence

__all__ = ["LaunchContext", "ClusterUnavailable", "ChildFailure",
           "free_port", "launch"]

_INIT_ERRORS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "Barrier timed out",
                "failed to connect", "Connection refused")


class ClusterUnavailable(RuntimeError):
    """The localhost jax.distributed cluster could not form (some
    sandboxes block even 127.0.0.1 gRPC) — an environment limitation,
    reported distinctly so callers can skip instead of fail."""


class ChildFailure(RuntimeError):
    """One or more launched processes raised / died / hung; the message
    carries every failing rank's traceback or exit status."""


@dataclasses.dataclass(frozen=True)
class LaunchContext:
    """What a launched target knows about its place in the cluster."""

    process_id: int
    num_processes: int
    coordinator: str
    devices_per_process: int
    args: tuple = ()


def free_port() -> int:
    """An OS-assigned free localhost TCP port for the coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_main(conn, target: Callable, ctx: LaunchContext,
                env: dict) -> None:
    """Child entry (spawn: a fresh interpreter — this module re-imports,
    but jax has NOT initialized a backend yet). Env pins must land before
    the first backend touch; results/errors ride the pipe."""
    try:
        os.environ.update(env)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "").split(
                " --xla_force_host_platform_device_count")[0]
            + f" --xla_force_host_platform_device_count="
              f"{ctx.devices_per_process}").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

        from photon_tpu.parallel.mesh import initialize_distributed

        try:
            ok = initialize_distributed(ctx.coordinator,
                                        ctx.num_processes, ctx.process_id,
                                        initialization_timeout=60)
        except Exception as e:  # noqa: BLE001 — classified below
            if any(p in str(e) for p in _INIT_ERRORS):
                conn.send(("cluster_unavailable",
                           f"{type(e).__name__}: {e}"))
                return
            raise
        if not ok:
            conn.send(("cluster_unavailable", "initialize_distributed "
                       "returned False for an explicit cluster"))
            return
        expect = ctx.devices_per_process * ctx.num_processes
        got = len(jax.devices())
        if got != expect:
            raise RuntimeError(
                f"rank {ctx.process_id}: global device count {got} != "
                f"{expect} — the mesh would differ across process counts")
        conn.send(("ok", target(ctx)))
    except BaseException as e:  # noqa: BLE001 — child edge: everything ships to the parent
        try:
            conn.send(("error",
                       f"{type(e).__name__}: {e}\n"
                       f"{traceback.format_exc()}"))
        except Exception:  # noqa: BLE001 — pipe gone: parent sees the dead child
            pass
    finally:
        conn.close()


def launch(target: Callable, n_processes: int, *,
           args: Sequence = (), total_devices: int = 8,
           timeout_s: float = 300.0,
           env: Optional[dict] = None) -> list:
    """Run ``target(ctx)`` in ``n_processes`` fresh spawn-context
    processes forming one jax.distributed cluster; return the per-rank
    results in rank order.

    ``target`` must be picklable (a module-level function — spawn
    children import its module fresh). ``total_devices`` must divide by
    ``n_processes``; each child gets ``total_devices // n_processes``
    virtual CPU devices so the GLOBAL mesh is identical at every process
    count. ``env`` adds/overrides child environment variables (fault
    knobs, barrier timeouts). Raises :class:`ClusterUnavailable` when the
    sandbox cannot form even a localhost cluster, :class:`ChildFailure`
    when any rank raises, dies, or exceeds ``timeout_s``.
    """
    n_processes = int(n_processes)
    if n_processes < 1:
        raise ValueError(f"n_processes must be >= 1, got {n_processes}")
    if total_devices % n_processes:
        raise ValueError(
            f"total_devices={total_devices} does not divide into "
            f"{n_processes} processes — the global mesh would change "
            "shape across process counts")
    coordinator = f"127.0.0.1:{free_port()}"
    mp = multiprocessing.get_context("spawn")
    child_env = dict(env or {})
    procs: list = []
    conns: list = []
    results: list = [None] * n_processes
    errors: list = []
    unavailable: list = []
    try:
        for rank in range(n_processes):
            ctx = LaunchContext(rank, n_processes, coordinator,
                                total_devices // n_processes, tuple(args))
            parent_conn, child_conn = mp.Pipe(duplex=False)
            p = mp.Process(target=_child_main,
                           args=(child_conn, target, ctx, child_env),
                           name=f"photon-launch-{rank}", daemon=True)
            p.start()
            child_conn.close()  # parent keeps only the read end
            procs.append(p)
            conns.append(parent_conn)
        import time

        deadline = time.monotonic() + float(timeout_s)
        for rank, conn in enumerate(conns):
            remaining = max(deadline - time.monotonic(), 0.0)
            if not conn.poll(remaining):
                errors.append(f"rank {rank}: no result within "
                              f"{timeout_s:.0f}s (hung or killed)")
                continue
            try:
                status, payload = conn.recv()
            except EOFError:
                errors.append(f"rank {rank}: died without a result "
                              f"(exitcode {procs[rank].exitcode})")
                continue
            if status == "ok":
                results[rank] = payload
            elif status == "cluster_unavailable":
                unavailable.append(f"rank {rank}: {payload}")
            else:
                errors.append(f"rank {rank}: {payload}")
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=30.0)
        for p in procs:
            if p.is_alive():  # terminate ignored: last resort, then join
                p.kill()
                p.join(timeout=10.0)
        for conn in conns:
            conn.close()
    if unavailable and not errors:
        raise ClusterUnavailable(
            "localhost jax.distributed cluster could not form:\n"
            + "\n".join(unavailable))
    if errors or unavailable:
        raise ChildFailure(
            f"{len(errors) + len(unavailable)}/{n_processes} launched "
            "processes failed:\n" + "\n".join(errors + unavailable))
    return results
