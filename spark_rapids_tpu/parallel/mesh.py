"""Device-mesh management + the engine-level ICI shuffle data plane.

This is where a *planned* query's ``ShuffleExchangeExec`` leaves the host
loop: the map outputs of chip ``d`` are shard ``d`` of one mesh-global
batch, taken where they lie, and a single compiled ``shard_map`` program
routes every row to its owner chip with ``lax.all_to_all`` over ICI
(``parallel/shuffle.py``'s tile protocol).  What the program leaves on
chip ``t`` is handed on there (``parallel/placement.py``): only the count
read crosses to the host.  The reference reaches the same point through
the UCX peer-to-peer transport (``RapidsShuffleClient.scala:476`` /
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
    host columns, ragged leaves).  Between executors on their own chips
    there is no other plane: the exchange exec counts the decline
    (``meshFallbacks``) and fails the query."""


class MeshCollectiveTimeout(MeshShuffleUnsupported):
    """A compiled mesh collective exceeded its deadline
    (``spark.rapids.tpu.mesh.collectiveDeadlineMs``).  Subclasses
    MeshShuffleUnsupported ON PURPOSE: the exchange exec's one catch
    counts it like any decline and fails the stage instead of hanging it
    — LOUDLY (``mesh_collective_timeouts_total`` counter + a fault-cat
    trace span), never silently."""


#: observability: exchanges that actually rode the mesh plane (tests assert
#: on this; the metrics layer reads it for the shuffle mode report)
STATS = {"mesh_exchanges": 0, "fallbacks": 0, "collective_timeouts": 0}

#: where the newest exchanges (newest last) left their data: the mesh's
#: per-device ``bytes_in_use`` right after the exchange program returned
#: (None where the backend reports no memory stats), the devices its
#: outputs lay on, the live rows it was handed at their width in its
#: arrays (``exchanged_bytes``), those of them that changed chips
#: (``sent_rows``, ``sent_bytes``) and, once the exchange exec has handed
#: them on, the devices the reduce partitions lie on
RECENT_EXCHANGES: collections.deque = collections.deque(maxlen=32)


def _lives_on(arrays) -> List[str]:
    from .placement import label
    return sorted({label(d) for a in arrays for d in a.devices()})


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
        f"mesh collective exceeded its deadline ({detail})")


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


def device_mesh(n_devices: Optional[int] = None, devices=None):
    """A 1-D ``jax.sharding.Mesh`` (axis "data") over ``devices``, or over
    the first ``n_devices`` (default: all) local devices; None when that is
    fewer than two.  Cached per device set."""
    if devices is None:
        import jax
        devs = jax.devices()
        n = n_devices or len(devs)
        if len(devs) < n:
            return None
        devices = devs[:n]
    if len(devices) < 2:
        return None
    key = tuple(d.id for d in devices)
    with _mesh_lock:
        m = _mesh_cache.get(key)
        if m is None:
            from jax.sharding import Mesh
            m = Mesh(np.array(list(devices)), ("data",))
            _mesh_cache[key] = m
        return m


# ---------------------------------------------------------------------------
# batch alignment (shards must agree on every leaf shape)
# ---------------------------------------------------------------------------

def _align_columns(cols: Sequence):
    """Align one column position across shards: byte-matrix widths and
    array slot widths to the max, recursively."""
    from ..columnar.column import DeviceColumn
    from ..columnar.encoded import DictEncodedColumn

    c0 = cols[0]
    if isinstance(c0, DictEncodedColumn):
        return list(cols)   # codes: nothing to align but the capacity
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
    per-shard capacity ``cap``: ``step(rows, pids, *leaves)`` over
    mesh-global arrays sharded on "data" (``rows``: int32[n_dev], the live
    rows of each shard; ``pids``: the target CHIP of every row) — the
    partition pass, the all_to_all, the received rows at the front.
    Returns ``(got[n_dev, n_dev], *leaves[n_dev * n_dev*cap, ...])``;
    ``got[t, s]`` is the rows chip t received from shard s (the counts the
    exchange moves anyway: nothing is computed for the record).  Separate
    from :func:`mesh_shuffle_batches` so the chip-compiler tests can lower
    the very program the exchange runs (tests/test_tpu_compile.py)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..shims import shard_map as _shim_shard_map
    from .shuffle import build_ici_shuffle
    shard_map = _shim_shard_map()  # version-shimmed (shims/, L6 analog)
    exchange = build_ici_shuffle(mesh, "data", n_dev, cap, with_counts=True)

    def step(rows, pids_, *leaves):
        valid = jnp.arange(cap, dtype=jnp.int32) < rows[0]
        arrays = {str(j): leaf for j, leaf in enumerate(leaves)}
        recv, _, got = exchange(arrays, valid, pids_)
        return (got[None], *(recv[str(j)] for j in range(nleaves)))

    return shard_map(step, mesh=mesh,
                     in_specs=(P("data"),) * (2 + nleaves),
                     out_specs=(P("data"),) * (1 + nleaves))


def mesh_shuffle_batches(mesh, batches: List, pids: List, nt: int):
    """Exchange ``n_dev`` per-shard batches into ``nt == n_dev`` target
    partitions through one compiled all_to_all program over ``mesh``.

    ``batches`` must be shape-aligned (``align_batches``), hold plain
    device columns (a dictionary is not row data: the caller exchanges the
    codes) and carry their host-known row counts; batch ``d`` and
    ``pids[d]`` — an int32 [capacity] array of target chips for its rows
    (dead rows' ids are ignored) — lie on chip ``d`` of ``mesh``, and what
    does not is brought there, counted (``placement.move``).  Returns one
    batch per target, living on the target's chip at the program's output
    capacity with its row count known, and the exchange's record (also
    appended to ``RECENT_EXCHANGES``; ``sent_bytes`` are the rows that
    changed chips at their width in the exchange's arrays).
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

    from ..columnar.batch import ColumnarBatch
    from . import placement

    n_dev = len(batches)
    if nt != n_dev:
        raise MeshShuffleUnsupported(
            f"targets {nt} != mesh devices {n_dev}")
    cap = batches[0].capacity
    names = batches[0].names
    chips = list(mesh.devices.flat)


    from ..observability import tracer as _trace
    from ..shims import tree_flatten, tree_unflatten
    with _trace.span("shuffle", "mesh_exchange.map"):
        # shard d is what chip d holds already; anything else is a
        # host-driven copy that the plan should not have needed
        batches = [placement.move(b, chips[d])
                   for d, b in enumerate(batches)]
        pids = [placement.put(p, chips[d]) for d, p in enumerate(pids)]
    leaves0, treedef = tree_flatten(batches[0].columns)
    folded_per_shard: List[List] = []
    ks: List[int] = []
    for b in batches:
        leaves, td = tree_flatten(b.columns)
        if td != treedef or len(leaves) != len(leaves0):
            raise MeshShuffleUnsupported(
                "shards disagree on batch treedef")
        folded = []
        for j, leaf in enumerate(leaves):
            f, k = _leaf_fold(leaf, cap)
            if len(ks) <= j:
                ks.append(k)
            folded.append(f)
        folded_per_shard.append(folded)

    # the shards ARE the mesh-global arrays: [n_dev*cap, k, ...]
    from jax.sharding import NamedSharding, PartitionSpec as P
    on_mesh = NamedSharding(mesh, P("data"))

    def whole(parts):
        return jax.make_array_from_single_device_arrays(
            (n_dev * parts[0].shape[0],) + tuple(parts[0].shape[1:]),
            on_mesh, parts)

    g_leaves = [whole([folded_per_shard[i][j] for i in range(n_dev)])
                for j in range(len(leaves0))]
    g_pids = whole(pids)
    g_rows = np.asarray([b.num_rows_int for b in batches], np.int32)
    out_cap = n_dev * cap

    # one compiled program per (mesh size, capacity, leaf signature) —
    # repeated collects of the same query reuse it (kernel_cache model)
    from ..sql.physical.kernel_cache import cached_jit
    key = ("MeshExchange", "exchange", n_dev, cap,
           tuple((tuple(g.shape), str(g.dtype)) for g in g_leaves))
    jitted = cached_jit(key, exchange_program(mesh, n_dev, cap,
                                              len(g_leaves)))

    from ..config import MESH_COLLECTIVE_DEADLINE_MS, RapidsConf
    deadline_s = int(RapidsConf.get_global().get(
        MESH_COLLECTIVE_DEADLINE_MS)) / 1e3

    def dispatch():
        with mesh:
            return jitted(g_rows, g_pids, *g_leaves)

    with _trace.span("shuffle", "mesh_exchange.collective"):
        got, *outs = _run_with_deadline(dispatch, deadline_s)
    with _trace.span("shuffle", "mesh_exchange.counts"):
        # the exchange's one read; waits for the program
        got = np.asarray(got).reshape(n_dev, n_dev)
    STATS["mesh_exchanges"] += 1
    counts = got.sum(axis=1)
    # a row's width in the exchange's arrays (validity, lengths and a
    # string's padded matrix included): what one live row puts on the wire
    row_bytes = sum(g.dtype.itemsize * (g.size // (n_dev * cap))
                    for g in g_leaves)
    crossed = int(got.sum() - np.trace(got))
    record = dict(bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                                for d in chips],
                  program_outputs_live_on=_lives_on(outs),
                  exchanged_bytes=int(got.sum()) * row_bytes,
                  sent_rows=crossed, sent_bytes=crossed * row_bytes)

    # Target t's rows are exactly shard t of every output (P("data") over
    # n_dev devices, out_cap rows each), and they stay where the collective
    # left them: the shard is taken as the single-device array it is.
    # Slicing the GLOBAL array instead leaves each batch spread over the
    # whole mesh, and the next stage then asks XLA to partition a
    # single-device program — which the chip refuses as soon as it holds a
    # Pallas kernel (the first four-chip run, PR 22).
    shards = [{s.device: s.data for s in g.addressable_shards} for g in outs]
    result = []
    for t in range(nt):
        leaves_t = []
        for j in range(len(outs)):
            leaf = shards[j][chips[t]]
            if ks[j] != 1:
                leaf = leaf.reshape((out_cap * ks[j],)
                                    + tuple(leaf.shape[2:]))
            leaves_t.append(leaf)
        cols = tree_unflatten(treedef, leaves_t)
        result.append(ColumnarBatch(
            names, cols, placement.put(np.int32(counts[t]), chips[t])
        ).with_known_rows(int(counts[t])))
    RECENT_EXCHANGES.append(record)
    return result, record
