"""Device-mesh helpers: single-slice ICI meshes and multi-host ICI×DCN.

The reference distributes with Spark RDD partitions over an Ethernet
cluster; photon-tpu uses a `jax.sharding.Mesh` and lets XLA place the
collectives. Conventions:

- axis ``"data"``: examples are sharded across it; gradient aggregation is
  a `psum` over this axis (the `treeAggregate` analog,
  reference: DistributedGLMLossFunction.calculate gradient treeAggregate).
  On a single slice this all-reduce rides the ICI.
- axis ``"replica"`` (multi-host): the slower DCN axis between slices/hosts.
  Examples shard over BOTH axes (`P(("replica", "data"))`) — each slice
  holds a contiguous row range, split again across its chips. A gradient
  psum over ``("replica", "data")`` lowers to a hierarchical all-reduce:
  reduce inside the slice over ICI first, then once across DCN per slice —
  the (d,)-vector crossing DCN once per iteration instead of the whole
  batch, exactly the reference's executor-tree→driver aggregation shape but
  compiler-scheduled.
- axis ``"entity"`` (optional, for very large random-effect spaces):
  per-entity model blocks are sharded across it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Re-exported from ONE place so every photon_tpu module (and the tests)
# imports the same shard_map.
from jax import shard_map  # noqa: F401


def vary_like(tree, *refs):
    """Cast every leaf of ``tree`` to vary over the manual (`shard_map`)
    axes that any leaf of ``refs`` varies over; identity outside shard_map.

    `shard_map` types each value by the manual axes it varies over, and
    `while_loop`/`scan` require a carry to keep its type. A carry built
    from fresh constants (`jnp.zeros`) is typed invariant, so a solve
    whose state differs per shard (the entity-sharded random-effect
    buckets) hands back a varying value the loop rejects. Solvers cast the
    initial carry HERE, where it is built, to the type the loop will hold."""
    want = frozenset().union(
        *(jax.typeof(r).vma for r in jax.tree_util.tree_leaves(refs)))
    if not want:
        return tree

    def cast(x):
        need = tuple(sorted(want - jax.typeof(x).vma))
        return jax.lax.pcast(x, need, to="varying") if need else x

    return jax.tree_util.tree_map(cast, tree)


def make_mesh(data_axis: str = "data", n_devices: int | None = None,
              devices=None) -> Mesh:
    """A 1-D mesh over (up to) ``n_devices`` devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (data_axis,))


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None,
                           initialization_timeout: float | None = None
                           ) -> bool:
    """Bring up the multi-host runtime (jax.distributed) — the analog of the
    reference's Spark driver/executor bootstrap, except the transport is
    XLA's DCN-aware runtime rather than RPC to a driver.

    With no arguments, reads the ``PHOTON_TPU_COORDINATOR`` /
    ``PHOTON_TPU_NUM_PROCESSES`` / ``PHOTON_TPU_PROCESS_ID`` knobs (the
    launcher exports them to its children) and, if those are unset too,
    defers entirely to `jax.distributed.initialize()`'s own cluster
    auto-detection (Cloud TPU pod metadata, SLURM, the JAX_* env vars) —
    a plain single-process environment fails that detection and returns
    False. With explicit arguments they are passed through. Returns True
    when the distributed runtime was initialized (including an explicit
    ``num_processes=1`` cluster-of-one — the bit-identity convention:
    every process count, 1 included, runs the SAME runtime + collectives
    stack, see docs/MULTIHOST.md).

    On the CPU backend the cross-process collectives implementation is
    pinned to gloo BEFORE backend init (the default CPU client refuses
    multi-process computations outright), which is what makes the
    1/2/4-process CPU spine both runnable and bit-identical.

    Validation is loud: a ``process_id`` outside ``[0, num_processes)``
    raises ValueError before any network traffic, and a second initialize
    in the same process raises RuntimeError with the fix spelled out
    instead of jax's opaque failure.
    """
    from photon_tpu.utils.env import get_raw

    if coordinator_address is None:
        coordinator_address = get_raw("PHOTON_TPU_COORDINATOR")
    if num_processes is None:
        raw = get_raw("PHOTON_TPU_NUM_PROCESSES")
        num_processes = int(raw) if raw is not None else None
    if process_id is None:
        raw = get_raw("PHOTON_TPU_PROCESS_ID")
        process_id = int(raw) if raw is not None else None

    if num_processes is not None and num_processes < 1:
        raise ValueError(
            f"num_processes must be >= 1, got {num_processes}")
    if process_id is not None:
        if num_processes is None:
            raise ValueError(
                "process_id given without num_processes — pass both (or "
                "set PHOTON_TPU_NUM_PROCESSES next to "
                "PHOTON_TPU_PROCESS_ID)")
        if not 0 <= process_id < num_processes:
            raise ValueError(
                f"process_id {process_id} out of range for "
                f"num_processes={num_processes} (ranks are "
                f"0..{num_processes - 1})")
    if distributed_client() is not None:
        raise RuntimeError(
            "jax.distributed is already initialized in this process — "
            "initialize_distributed must run exactly once, before any "
            "backend use. Reuse the existing runtime, or call "
            "jax.distributed.shutdown() first if you really mean to "
            "re-form the cluster (tests: run each cluster member in a "
            "fresh process, e.g. via parallel.launch).")

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = initialization_timeout
    if not kwargs and os.environ.get("JAX_COORDINATOR_ADDRESS") is None \
            and not _cluster_detectable():
        return False
    _pin_cpu_collectives()
    try:
        jax.distributed.initialize(**kwargs)
        return True
    except (RuntimeError, ValueError):
        # no detectable cluster (auto-detection path only — explicit
        # arguments re-raise nothing here because jax only raises for
        # malformed clusters, which the validation above already caught)
        if kwargs:
            raise
        return False


def distributed_client():
    """The live jax.distributed client, or None — the one place the
    private global_state handle is read (double-init refusal above, the
    checkpoint store's coordination-service barrier)."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:
        return None


def cluster_barrier(tag: str, timeout_s: float = 60.0) -> float:
    """A TIMED cluster-wide barrier: every process blocks until all ranks
    arrive, and the wait is measured into a ``parallel.barrier_wait``
    span (attrs carry the tag) — the signal `telemetry.aggregate` uses to
    name the straggler rank (the rank that waits LEAST is the one the
    others waited for). Returns this rank's wait in seconds; free no-op
    (0.0, still spanned) on a single-process cluster. Prefers the
    coordination-service barrier, falling back to a device-level sync
    like the checkpoint store's commit barrier."""
    import time

    from photon_tpu import telemetry

    t0 = time.perf_counter()
    with telemetry.span("parallel.barrier_wait", tag=tag):
        if jax.process_count() > 1:
            client = distributed_client()
            if client is not None:
                client.wait_at_barrier(tag, int(timeout_s * 1000))
            else:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(tag)
    return time.perf_counter() - t0


def _pin_cpu_collectives() -> None:
    """CPU backend only: pin gloo for cross-process collectives BEFORE the
    backend initializes. Its ring's reduction order depends only on the
    GLOBAL rank count, so the same 8-device mesh produces bit-identical
    psums whether it is split 1, 2, or 4 ways (the multihost_e2e
    acceptance bar). No-op on TPU backends."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms:
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _cluster_detectable() -> bool:
    """Whether JAX's ClusterEnv auto-detection would find a cluster, without
    paying its (possibly blocking) metadata queries in plain local runs."""
    try:
        from jax._src.clusters import ClusterEnv

        return any(c.is_env_present() for c in ClusterEnv._cluster_types)
    except Exception:
        return False


def make_hybrid_mesh(n_replicas: int | None = None,
                     dcn_axis: str = "replica", ici_axis: str = "data",
                     devices=None) -> Mesh:
    """A 2-D (replica × data) mesh with the replica axis on DCN.

    Multi-host: uses `mesh_utils.create_hybrid_device_mesh`, which orders
    devices so that the ``dcn_axis`` strides across slices (DCN) and the
    ``ici_axis`` stays inside each slice (ICI) — a psum over ``ici_axis``
    then never leaves the slice, and a psum over both axes lowers
    hierarchically. Single-host (tests, virtual CPU meshes): plain reshape,
    which preserves the same program semantics without the topology.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if n_replicas is None:
        # One replica per slice on multi-slice topologies; otherwise one per
        # host process (single-slice pods / CPU test meshes).
        n_replicas = n_slices if n_slices > 1 else max(jax.process_count(), 1)
    n = len(devices)
    if n % n_replicas != 0:
        raise ValueError(f"{n} devices do not divide into "
                         f"{n_replicas} replicas")
    per = n // n_replicas
    if n_slices > 1:
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_hybrid_device_mesh(
            (per,), (n_replicas,), devices=devices)
        grid = grid.reshape(n_replicas, per)
    else:
        grid = np.asarray(devices).reshape(n_replicas, per)
    return Mesh(grid, (dcn_axis, ici_axis))


def data_sharding(mesh: Mesh, axis=None) -> NamedSharding:
    """Shard the leading (example) dimension across ALL mesh axes (for a
    hybrid mesh: slice-major over DCN, chip-minor over ICI), or across the
    given axis/axes only."""
    spec = tuple(mesh.axis_names) if axis is None else axis
    return NamedSharding(mesh, P(spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, m: int) -> int:
    """Examples are padded (with weight 0) so shards are equal-size/static."""
    return ((n + m - 1) // m) * m


# --------------------------------------------------------------------------
# Row-slot helpers for the STREAMED mesh regime (optim/streamed.py): a host
# chunk is split into one equal row slice per device slot — slot j of a
# D-device mesh owns rows [j·s, (j+1)·s) of the (padded) chunk — and each
# process device_puts only the slots its own devices own, so on multi-host
# the features a process uploads are exactly its host-local row range and
# never cross DCN. The per-chunk partial sums then accumulate device-local
# and close with ONE hierarchical psum per evaluation: reduce over the ICI
# axis inside the slice, one (d,) vector across DCN — the literal
# treeAggregate shape of the docstring above, driven chunk by chunk.


def flat_mesh_devices(mesh: Mesh) -> list:
    """Mesh devices flattened in P(axis_names) shard order (row-major over
    the axis grid) — slot j of this list owns row-shard j."""
    return list(np.asarray(mesh.devices).reshape(-1))


def local_row_slots(mesh: Mesh) -> list:
    """Global device-slot indices owned by THIS process, in slot order."""
    proc = jax.process_index()
    return [j for j, d in enumerate(flat_mesh_devices(mesh))
            if d.process_index == proc]


def shard_rows(host, mesh: Mesh, pad_rows: int | None = None):
    """Row-shard a host array over ALL mesh axes: per-slot host slices are
    device_put straight onto their device (multi-host: local slots only —
    other processes' rows are never touched) and assembled with
    `make_array_from_single_device_arrays`. Rows pad with zeros to
    ``pad_rows`` (default: the next device multiple) — zero rows carry
    weight 0 in every GLMBatch, so no reduction sees them."""
    host = np.asarray(host)
    devices = flat_mesh_devices(mesh)
    n_dev = len(devices)
    n = host.shape[0]
    n_pad = pad_to_multiple(max(n, 1), n_dev) if pad_rows is None \
        else int(pad_rows)
    s = n_pad // n_dev
    tail = host.shape[1:]
    arrays = []
    for j in local_row_slots(mesh):
        lo, hi = j * s, min((j + 1) * s, n)
        if hi - lo == s:
            buf = host[lo:hi]
        else:
            buf = np.zeros((s,) + tail, host.dtype)
            if hi > lo:
                buf[:hi - lo] = host[lo:hi]
        arrays.append(jax.device_put(buf, devices[j]))
    return jax.make_array_from_single_device_arrays(
        (n_pad,) + tail, NamedSharding(mesh, P(tuple(mesh.axis_names))),
        arrays)


def shard_local_rows(local, mesh: Mesh):
    """Re-shard a (n_local_slots, s, ...) host stack (the layout
    `fetch_local_rows` returns — one row-slice per LOCAL device slot, in
    slot order) back onto the mesh without touching other processes'
    rows."""
    local = np.asarray(local)
    devices = flat_mesh_devices(mesh)
    slots = local_row_slots(mesh)
    s = local.shape[1]
    arrays = [jax.device_put(local[k], devices[j])
              for k, j in enumerate(slots)]
    return jax.make_array_from_single_device_arrays(
        (len(devices) * s,) + local.shape[2:],
        NamedSharding(mesh, P(tuple(mesh.axis_names))), arrays)


def shard_stacked(host, mesh: Mesh):
    """Shard a host ``(n_dev, ...)`` stack one leading index per device
    slot: slot j gets ``host[j:j+1]`` device_put straight onto its device
    (multi-host: local slots only). The upload form of per-shard
    structure blocks whose leading axis IS the shard axis — e.g. a
    ShardedBlockedEllRows chunk's ELL/occurrence buckets in the
    mesh-streamed regime — mirroring `shard_rows` for row-major data."""
    host = np.asarray(host)
    devices = flat_mesh_devices(mesh)
    if host.shape[0] != len(devices):
        raise ValueError(
            f"stacked leading axis {host.shape[0]} != {len(devices)} mesh "
            "devices; rebuild the structure for this mesh")
    arrays = [jax.device_put(host[j:j + 1], devices[j])
              for j in local_row_slots(mesh)]
    return jax.make_array_from_single_device_arrays(
        host.shape, NamedSharding(mesh, P(tuple(mesh.axis_names))), arrays)


def fetch_local_rows(arr, mesh: Mesh) -> np.ndarray:
    """The inverse of `shard_local_rows`: this process's row shards of a
    P(axes)-sharded array as one (n_local_slots, s, ...) numpy stack in
    slot order — the host-side cache layout of the streamed solvers'
    margin chains."""
    shards = sorted(arr.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
    return np.stack([np.asarray(sh.data) for sh in shards])


def compact_rows(tree, idx, pad_rows: int | None = None,
                 mesh: Mesh | None = None, pad_mode: str = "zero"):
    """Gather leading-axis rows ``idx`` from every leaf of a device tree
    into a dense zero-padded ``(pad_rows, ...)`` block — the straggler
    repack of the random-effect pipeline (game/random_effect.py): the
    unconverged tail of a capped vmapped pass is compacted into one small
    dense block and re-solved to full depth.

    The gather runs ON DEVICE (one fancy-index program per leaf shape; no
    host round-trip of the feature blocks), so ``idx`` may index a
    mesh-sharded entity axis on any single-slice/addressable mesh. With
    ``mesh`` given the compacted block is re-sharded across all mesh axes
    (``data_sharding``) so the tail pass runs sharded exactly like the
    first pass; callers routing through ``dispatch_chunked`` pass
    ``mesh=None`` and let the dispatcher place the block. Zero-padded rows
    carry weight 0 in every GLMBatch, so no reduction sees them.

    ``pad_mode="edge"`` repeats the LAST gathered row into the pad instead
    of zeros — for lock-step LANE consumers (the tuner's survivor
    re-solve), where a zero-regularization zero-weight pad lane would be
    the slowest-converging lane in the chunk and drag the whole lock-step
    program to its straggler budget; a duplicate of a real survivor
    converges exactly as fast as its original.
    """
    if pad_mode not in ("zero", "edge"):
        raise ValueError(f"pad_mode must be 'zero' or 'edge', got {pad_mode!r}")
    idx = idx if isinstance(idx, jax.Array) else jnp.asarray(
        np.asarray(idx), jnp.int32)
    n = int(idx.shape[0])
    target = n if pad_rows is None else int(pad_rows)
    if n == 0 and target > 0 and pad_mode == "edge":
        raise ValueError("pad_mode='edge' needs at least one gathered row")

    def take(x):
        g = jnp.take(x, idx, axis=0)
        if target != n:
            widths = [(0, target - n)] + [(0, 0)] * (g.ndim - 1)
            g = jnp.pad(g, widths, mode=("edge" if pad_mode == "edge"
                                         else "constant"))
        return g

    out = jax.tree_util.tree_map(take, tree)
    if mesh is not None:
        out = jax.device_put(out, data_sharding(mesh))
    return out


# ----------------------------------------------------------------- contracts
# The docstring's treeAggregate claim — ONE variadic psum per evaluation,
# hierarchical over a hybrid mesh — as enforced law (see
# photon_tpu/analysis; tests/test_multihost.py pins the same fact).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402


def _contract_mesh_vg(mesh, axis_name):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    d = 6
    # l2 as np.float32 (make_objective's canon): a Python-float leaf is
    # weak-typed and the retrace-hazard rule rejects it.
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=np.float32(0.5),
                    axis_name=axis_name)
    rows = P(axis_name if isinstance(axis_name, tuple) else (axis_name,))

    def vg(b, w):
        return shard_map(lambda b, w: obj.value_and_grad(w, b),
                         mesh=mesh, in_specs=(rows, P()),
                         out_specs=(P(), P()))(b, w)

    rng = np.random.RandomState(0)
    n = 8 * int(mesh.devices.size)
    from photon_tpu.data.dataset import make_batch

    batch = make_batch(rng.randn(n, d).astype(np.float32),
                       (rng.rand(n) < 0.5).astype(np.float32))
    return vg, (batch, jnp.zeros((d,), jnp.float32))


@register_contract(
    name="mesh_value_and_grad",
    description="shard_map value_and_grad over the data axis: value and "
                "gradient partials ride ONE variadic psum per evaluation",
    collectives={"psum": 1}, tags=("resident", "mesh"))
def _contract_mesh_value_and_grad():
    return _contract_mesh_vg(make_mesh(), "data")


@register_contract(
    name="hybrid_mesh_value_and_grad",
    description="the 2-D replica(DCN) x data(ICI) mesh: the psum over BOTH "
                "axes is still ONE equation (hierarchical lowering is the "
                "backend's job, the contract is the single collective)",
    collectives={"psum": 1}, tags=("resident", "mesh"))
def _contract_hybrid_mesh_value_and_grad():
    n_dev = len(jax.devices())
    mesh = make_hybrid_mesh(n_replicas=2 if n_dev % 2 == 0 else 1)
    return _contract_mesh_vg(mesh, ("replica", "data"))


@register_contract(
    name="multihost_grad_only_dcn",
    description="the multi-process spine's wire bill (round 17): a sharded "
                "evaluation over a feature block 100x the model size still "
                "closes with ONE psum whose payload is the (d,) gradient "
                "partial + scalar value — features ingest on their owning "
                "process and NEVER ride a collective "
                "(tests/test_multihost.py prices the payload through "
                "profiling.model: O(d) bytes per evaluation, not O(n*d))",
    collectives={"psum": 1}, tags=("mesh", "multihost", "streamed"))
def _contract_multihost_grad_only_dcn():
    import jax.numpy as jnp

    from photon_tpu.data.dataset import make_batch
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    mesh = make_mesh()
    axes = tuple(mesh.axis_names)
    # d=48 / 128 rows per shard: the per-shard feature bytes dwarf the
    # (d+1)-float psum payload by >100x, so the byte-pricing test has an
    # unambiguous margin to pin (not a d ~ n coincidence).
    d = 48
    n = 128 * int(mesh.devices.size)
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=np.float32(0.5),
                    axis_name=axes)
    rows = P(axes)

    def vg(b, w):
        return shard_map(lambda b, w: obj.value_and_grad(w, b),
                         mesh=mesh, in_specs=(rows, P()),
                         out_specs=(P(), P()))(b, w)

    rng = np.random.RandomState(17)
    batch = make_batch(rng.randn(n, d).astype(np.float32),
                       (rng.rand(n) < 0.5).astype(np.float32))
    return vg, (batch, jnp.zeros((d,), jnp.float32))
