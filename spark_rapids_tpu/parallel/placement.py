"""Where partitions live when this host is several executors.

``spark.executor.instances = n`` (Spark's own key; n > 1) on a host that
shows at least n chips makes the process n executors, one chip each:
partition ``p`` of a relation, and reduce partition ``t`` of an exchange,
live on chip ``p % n``.  A jitted program runs where its committed
operands lie, so every stage of a task runs on the chip its input was
uploaded to or its exchange left it on; nothing here moves a batch unless
asked.  With one executor (the default), a host with fewer chips, or a job
that spans several slices, ``chips`` is the home device alone and none of
this does anything.

The layout decides the plane, no switch does: an exchange whose partitions
live on more than one chip is one ``all_to_all`` program over them
(``parallel/mesh.py``), whatever ``spark.rapids.shuffle.mode`` says.

Only three things bring data off its chip: an exchange's count read, a
terminal that by its nature gathers (broadcast, limit, sort sample, an
exchange to one partition: :func:`gather`), and the final collect.  Any
other move of a batch from one chip to another is counted
(``STATS["cross_chip_copies"]``): it should read 0.

Nothing here is remembered between calls: the layout is read from the conf
of the call, or of the task that runs on this thread, every time, so a
session of one executor after a session of four sees none of it.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

#: batches that crossed chips outside an exchange program and outside a
#: terminal's gather, since the process started
STATS = {"cross_chip_copies": 0}


def chips(conf=None) -> Tuple:
    """The chips partitions are spread over under ``conf`` (default: the
    conf of the task on this thread, else the global one): one per
    executor, or the home device alone."""
    from ..config import (EXECUTOR_INSTANCES, SHUFFLE_TOPOLOGY_SLICES,
                          RapidsConf)
    from ..memory.device import DeviceManager
    from ..sql.physical import kernel_cache
    if conf is None:
        from ..sql.physical.base import TaskContext
        task = TaskContext.current()
        conf = task.conf if task is not None else RapidsConf.get_global()
    dm = DeviceManager.get()
    n = int(conf.get(EXECUTOR_INSTANCES))
    if (n < 2 or int(conf.get(SHUFFLE_TOPOLOGY_SLICES)) > 1
            or len(dm.chips) < n):
        n = 1
    # one executable a program for all of them, or jax's own keys again
    kernel_cache.share_executables(dm.chips[:n])
    return dm.chips[:n]


def spread() -> bool:
    """Whether the task on this thread runs under a layout of several
    chips (the hot paths that guard against mixed devices ask this)."""
    return len(chips()) > 1


def home_chip(pid: int, conf=None):
    """Chip of partition ``pid``, or None where partitions are not spread
    (the caller then leaves placement to jax, as ever)."""
    cs = chips(conf)
    return cs[pid % len(cs)] if len(cs) > 1 else None


def chip_of(tree):
    """The chip the first device array of ``tree`` (a batch, a column, a
    list of them) lies on, or None (host arrays, nothing at all)."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            return next(iter(leaf.devices()))
    return None


def beside(tree):
    """Context in which eager glue (an ``arange``, a constant) is made on
    the chip ``tree`` lies on and not on chip 0, from where every use
    would copy it over; nothing where partitions are not spread."""
    import contextlib
    chip = chip_of(tree) if spread() else None
    if chip is None:
        return contextlib.nullcontext()
    import jax
    return jax.default_device(chip)


def label(chip) -> str:
    return f"{chip.platform}:{chip.id}"


def put(tree, chip):
    """``tree`` committed to ``chip`` (no copy for what lies there)."""
    import jax
    return jax.device_put(tree, chip)


def move(batch, chip, terminal: bool = False):
    """``batch`` on ``chip``: itself if it lies there, else a host-driven
    copy (span ``srt:d2h:mesh_gather``) — a terminal's gather, or a copy
    that the plan should not have needed, which is counted."""
    import jax
    src = chip_of(batch)
    if src is None or src == chip:
        return batch
    if not terminal:
        STATS["cross_chip_copies"] += 1
    from ..observability import tracer as _trace
    with _trace.span("d2h", "mesh_gather", src=label(src), chip=label(chip)):
        moved = jax.device_put(batch, chip)
    known = getattr(batch, "_nrows_host", None)
    return moved.with_known_rows(known) if known is not None else moved


def gather(batches: Sequence, chip=None, terminal: bool = True) -> List:
    """``batches`` on one chip (the first's, if none is given).  Does
    nothing where partitions are not spread."""
    if len(batches) < 2 and chip is None or not spread():
        return list(batches)
    if chip is None:
        chip = next((c for c in map(chip_of, batches) if c is not None),
                    None)
        if chip is None:
            return list(batches)
    return [move(b, chip, terminal) for b in batches]
