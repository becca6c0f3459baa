"""Device-mesh management + the engine-level ICI shuffle data plane.

This is where a *planned* query's ``ShuffleExchangeExec`` leaves the host
loop: the N map-side batches become one mesh-sharded global batch, and a
single compiled ``shard_map`` program routes every row to its owner chip
with ``lax.all_to_all`` over ICI (``parallel/shuffle.py``'s tile protocol),
compacting received rows on-chip.  The reference reaches the same point
through the UCX peer-to-peer transport (``RapidsShuffleClient.scala:476`` /
``UCX.scala:1119``); on TPU the interconnect is driven by XLA collectives
inside the program instead of host-driven RDMA.

Batches are pytrees of row-major leaves.  Every leaf's leading dim is a
multiple of the batch capacity (struct children: cap; array children:
cap*width; string matrices: [cap, width]), so each leaf reshapes to
[cap, k, ...] for the row-exchange and back afterwards — nested types ride
the same plane as flat columns.
"""

from __future__ import annotations

import collections
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np


class MeshShuffleUnsupported(Exception):
    """Raised when a batch cannot ride the mesh data plane (object-dtype
    host columns, ragged leaves); callers fall back to the local plane."""


class MeshCollectiveTimeout(MeshShuffleUnsupported):
    """A compiled mesh collective exceeded its deadline
    (``spark.rapids.tpu.mesh.collectiveDeadlineMs``).  Subclasses
    MeshShuffleUnsupported ON PURPOSE: the exchange exec's existing
    fallback catch degrades the stage to the local/TCP plane instead of
    hanging it — but LOUDLY (``mesh_collective_timeouts_total`` counter
    + a fault-cat trace span), never silently."""


#: observability: exchanges that actually rode the mesh plane (tests assert
#: on this; the metrics layer reads it for the shuffle mode report)
STATS = {"mesh_exchanges": 0, "fallbacks": 0, "collective_timeouts": 0}

#: where the newest exchanges (newest last) left their data: the mesh's
#: per-device ``bytes_in_use`` right after the exchange program returned
#: (None where the backend reports no memory stats), the devices its
#: outputs lay on, and the devices the batches handed on lie on
RECENT_EXCHANGES: collections.deque = collections.deque(maxlen=32)


def _lives_on(arrays) -> List[str]:
    return sorted({f"{d.platform}:{d.id}" for a in arrays
                   for d in a.devices()})


def _collective_timed_out(detail: str) -> MeshCollectiveTimeout:
    """The LOUD part of the degrade path, shared by the real watchdog
    and the chaos site: counter + fault span, then the typed timeout."""
    from ..observability import metrics as _om
    from ..observability import tracer as _trace
    STATS["collective_timeouts"] += 1
    _om.inc("mesh_collective_timeouts_total")
    with _trace.span("fault", "mesh.collective.timeout", detail=detail):
        pass    # a marker: the time went into the abandoned collective
    return MeshCollectiveTimeout(
        f"mesh collective exceeded its deadline ({detail}); "
        f"degrading stage to the local plane")


def _run_with_deadline(fn, deadline_s: float):
    """Cooperative collective watchdog: a compiled program cannot be
    recalled once dispatched, so the call runs on a worker thread and a
    deadline overrun abandons it (the thread parks on the runtime; the
    stage degrades instead of hanging).  deadline_s <= 0 = inline."""
    if deadline_s <= 0:
        return fn()
    box: dict = {}
    done = threading.Event()

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — marshalled to caller
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, name="srt-mesh-collective",
                         daemon=True)
    t.start()
    if not done.wait(deadline_s):
        raise _collective_timed_out(f"deadline {deadline_s:.3f}s")
    if "err" in box:
        raise box["err"]
    return box["out"]


_mesh_lock = threading.Lock()
_mesh_cache: dict = {}


def device_mesh(n_devices: Optional[int] = None):
    """A 1-D ``jax.sharding.Mesh`` over the local devices (axis "data"),
    or None when only one device is visible.  Cached per size."""
    import jax
    devs = jax.devices()
    n = n_devices or len(devs)
    if n < 2 or len(devs) < n:
        return None
    with _mesh_lock:
        m = _mesh_cache.get(n)
        if m is None:
            from jax.sharding import Mesh
            m = Mesh(np.array(devs[:n]), ("data",))
            _mesh_cache[n] = m
        return m


# ---------------------------------------------------------------------------
# batch alignment (shards must agree on every leaf shape)
# ---------------------------------------------------------------------------

def _align_columns(cols: Sequence):
    """Align one column position across shards: byte-matrix widths and
    array slot widths to the max, recursively."""
    from ..columnar.column import DeviceColumn

    c0 = cols[0]
    if c0.is_array_like:
        w = max(c.array_width for c in cols)
        cols = [c.with_array_width(w) for c in cols]
        kids = [_align_columns([c.children[k] for c in cols])
                for k in range(len(cols[0].children))]
        return [
            DeviceColumn(c.dtype, c.data, c.validity, c.lengths, c.aux,
                         tuple(kids[k][i] for k in range(len(kids))))
            for i, c in enumerate(cols)]
    if c0.data is None and c0.children:  # struct
        kids = [_align_columns([c.children[k] for c in cols])
                for k in range(len(cols[0].children))]
        return [
            DeviceColumn(c.dtype, None, c.validity, c.lengths, c.aux,
                         tuple(kids[k][i] for k in range(len(kids))))
            for i, c in enumerate(cols)]
    if c0.data is not None and c0.data.ndim == 2:
        import jax.numpy as jnp
        w = max(int(c.data.shape[1]) for c in cols)
        return [
            c if int(c.data.shape[1]) == w else
            DeviceColumn(c.dtype, jnp.pad(
                c.data, ((0, 0), (0, w - int(c.data.shape[1])))),
                c.validity, c.lengths, c.aux, c.children)
            for c in cols]
    return list(cols)


def align_batches(batches: List) -> List:
    """Repad a list of same-schema batches to one shared shape signature
    (common capacity bucket, common string/array widths)."""
    from ..columnar.batch import ColumnarBatch

    cap = max(b.capacity for b in batches)
    batches = [b.repadded(cap) if b.capacity != cap else b for b in batches]
    ncols = batches[0].num_cols
    per_col = [_align_columns([b.columns[ci] for b in batches])
               for ci in range(ncols)]
    return [ColumnarBatch(batches[0].names,
                          tuple(per_col[ci][i] for ci in range(ncols)),
                          b.num_rows)
            for i, b in enumerate(batches)]


# ---------------------------------------------------------------------------
# the mesh exchange
# ---------------------------------------------------------------------------

def _leaf_fold(leaf, cap: int):
    """Reshape a row-major leaf to [cap, k, ...]; returns (folded, k)."""
    if getattr(leaf, "dtype", None) == object:
        raise MeshShuffleUnsupported("object-dtype host column")
    m = int(leaf.shape[0])
    if m == cap:
        return leaf, 1
    if m % cap != 0:
        raise MeshShuffleUnsupported(
            f"leaf leading dim {m} not a multiple of capacity {cap}")
    k = m // cap
    return leaf.reshape((cap, k) + tuple(leaf.shape[1:])), k


def exchange_program(mesh, n_dev: int, cap: int, nleaves: int):
    """The (un-jitted) mesh exchange step for ``nleaves`` folded leaves of
    per-shard capacity ``cap``: ``step(valid, pids, *leaves)`` over
    mesh-global arrays sharded on "data" — all_to_all row exchange, then
    on-chip compaction of the received rows.  Returns
    ``(count[n_dev], *leaves[n_dev * n_dev*cap, ...])``.  Separate from
    :func:`mesh_shuffle_batches` so the chip-compiler tests can lower the
    very program the exchange runs (tests/test_tpu_compile.py)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.join import compact_indices
    from ..shims import shard_map as _shim_shard_map
    from .shuffle import build_ici_shuffle
    shard_map = _shim_shard_map()  # version-shimmed (shims/, L6 analog)
    exchange = build_ici_shuffle(mesh, "data", n_dev, cap)

    def step(valid, pids_, *leaves):
        arrays = {str(j): leaf for j, leaf in enumerate(leaves)}
        recv, rvalid = exchange(arrays, valid, pids_)
        # on-chip compaction: received rows to the front, count live
        perm = compact_indices(jnp, rvalid)
        out = [jnp.take(recv[str(j)], perm, axis=0) for j in range(nleaves)]
        count = jnp.sum(rvalid).astype(jnp.int32)
        return (count[None], *out)

    return shard_map(step, mesh=mesh,
                     in_specs=(P("data"),) * (2 + nleaves),
                     out_specs=(P("data"),) * (1 + nleaves))


def mesh_shuffle_batches(mesh, batches: List, pids: List, nt: int) -> List:
    """Exchange ``n_dev`` per-shard batches into ``nt == n_dev`` target
    partitions through one compiled all_to_all program over ``mesh``.

    ``batches`` must be shape-aligned (``align_batches``); ``pids[i]`` is an
    int32 [capacity] array of target partitions for shard i's rows (dead
    rows' ids are ignored).  Returns one (shrunk) batch per target.
    """
    # lifecycle poll site `mesh` — the one chokepoint family PR 10 never
    # covered: a cancelled query abandons the exchange BEFORE dispatching
    # a compiled collective it could not recall.  Sits ahead of every
    # device check so single-device tests reach it too.
    from ..robustness import faults as _faults
    from ..serving import lifecycle as _lc
    _lc.check_cancel("mesh")
    if _faults.CHAOS["on"] and _faults.should_fire(
            "mesh.collective.timeout", n_dev=len(batches)):
        raise _collective_timed_out("chaos-injected")
    import jax
    import jax.numpy as jnp

    from ..columnar.batch import ColumnarBatch

    n_dev = len(batches)
    if nt != n_dev:
        raise MeshShuffleUnsupported(
            f"targets {nt} != mesh devices {n_dev}")
    cap = batches[0].capacity
    names = batches[0].names

    from ..shims import tree_flatten, tree_unflatten
    leaves0, treedef = tree_flatten(batches[0].columns)
    folded_per_shard: List[List] = []
    ks: List[int] = []
    for b in batches:
        leaves, td = tree_flatten(b.columns)
        if td != treedef or len(leaves) != len(leaves0):
            raise MeshShuffleUnsupported("shards disagree on batch treedef")
        folded = []
        for j, leaf in enumerate(leaves):
            f, k = _leaf_fold(leaf, cap)
            if len(ks) <= j:
                ks.append(k)
            folded.append(f)
        folded_per_shard.append(folded)

    # stack shards into mesh-global arrays: [n_dev*cap, k, ...]
    g_leaves = [jnp.concatenate([folded_per_shard[i][j]
                                 for i in range(n_dev)])
                for j in range(len(leaves0))]
    g_pids = jnp.concatenate([jnp.asarray(p).astype(jnp.int32)
                              for p in pids])
    g_valid = jnp.concatenate([b.row_mask() for b in batches])

    out_cap = n_dev * cap

    # one compiled program per (mesh size, capacity, leaf signature) —
    # repeated collects of the same query reuse it (kernel_cache model)
    from ..sql.physical.kernel_cache import cached_jit
    key = ("mesh_shuffle", n_dev, cap,
           tuple((tuple(g.shape), str(g.dtype)) for g in g_leaves))
    jitted = cached_jit(key, exchange_program(mesh, n_dev, cap,
                                              len(g_leaves)))

    from ..config import MESH_COLLECTIVE_DEADLINE_MS, RapidsConf
    deadline_s = int(RapidsConf.get_global().get(
        MESH_COLLECTIVE_DEADLINE_MS)) / 1e3

    # the stacked inputs lie on the home device (every upload and every
    # earlier exchange's output does): lay them out over the mesh
    # explicitly rather than leave it to jit, which refuses arrays that
    # are committed to one device
    from jax.sharding import NamedSharding, PartitionSpec as P
    on_mesh = NamedSharding(mesh, P("data"))
    g_valid, g_pids, *g_leaves = jax.device_put(
        [g_valid, g_pids, *g_leaves], on_mesh)

    def dispatch():
        with mesh:
            return jitted(g_valid, g_pids, *g_leaves)

    from ..observability import tracer as _trace
    with _trace.span("shuffle", "mesh_exchange", partitions=nt,
                     devices=n_dev):
        counts, *outs = _run_with_deadline(dispatch, deadline_s)
        counts = np.asarray(counts)   # waits for the program: outputs exist
    STATS["mesh_exchanges"] += 1
    record = {"bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                               for d in mesh.devices.flat],
              "program_outputs_live_on": _lives_on(outs)}

    # Target t's rows are exactly shard t of every output (P("data") over
    # n_dev devices, out_cap rows each).  Take that shard where it lies and
    # bring it to the engine's home device, where every stage program runs:
    # slicing the GLOBAL array instead leaves each batch spread over the
    # whole mesh, and the next stage then asks XLA to partition a
    # single-device program — which the chip refuses as soon as it holds a
    # Pallas kernel ("Mosaic kernels cannot be automatically partitioned":
    # the first four-chip run, PR 22).
    from ..memory.device import DeviceManager
    home = DeviceManager.get().device
    shards = [{(s.index[0].start or 0) // out_cap: s.data
               for s in g.addressable_shards} for g in outs]

    result = []
    for t in range(nt):
        leaves_t = []
        for j in range(len(outs)):
            leaf = jax.device_put(shards[j][t], home)
            if ks[j] != 1:
                leaf = leaf.reshape((out_cap * ks[j],)
                                    + tuple(leaf.shape[2:]))
            leaves_t.append(leaf)
        cols = tree_unflatten(treedef, leaves_t)
        result.append(ColumnarBatch.make(names, cols,
                                         int(counts[t])).shrunk())
    record["batches_handed_on_live_on"] = _lives_on(
        leaf for b in result for leaf in jax.tree_util.tree_leaves(b.columns))
    RECENT_EXCHANGES.append(record)
    return result
