"""ICI shuffle data plane — the on-pod replacement for the reference's
UCX peer-to-peer transfers (SURVEY §2.8 TPU-native note): rows move between
chips INSIDE the compiled program via ``jax.lax.all_to_all`` over a device
mesh, so the exchange rides ICI links with XLA-scheduled overlap instead of
host round-trips.

Mechanics (static shapes throughout):

* each shard orders its rows by target chip with the local plane's
  partition pass (``ops/join.py:partition_indices``: a cumsum per target,
  no sort), gathers every array ONCE into that order, and cuts the
  ``[n_dev, quota]`` tile out of it with contiguous slices (quota = local
  capacity, the worst case of every row routing to one target);
* one tiled ``all_to_all`` flips the tile axis: row-block t of shard s
  lands on shard t as block s; the row counts travel the same way;
* the receiver writes block after block at the running offset of the rows
  before it (contiguous copies again), so its rows come out at the front,
  in source order, and everything past them zeroed.

Works for any pytree of row-major arrays (1-D fixed columns, 2-D byte
matrices), which is exactly the device column layout.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def build_ici_shuffle(mesh, axis_name: str, n_dev: int, quota: int,
                      with_counts: bool = False):
    """Returns a function usable inside shard_map:
    (arrays: dict[str, [rows(,k)]], valid: [rows], pids: [rows]) ->
    (arrays received, valid received) with capacity n_dev*quota; the
    received rows lie at the front (``valid received`` is a prefix).
    ``with_counts`` adds a third result, int32[n_dev]: the rows received
    from each shard (the counts the exchange moves anyway)."""
    import jax
    import jax.numpy as jnp

    from ..ops.join import partition_indices

    def exchange(arrays: Dict[str, "jnp.ndarray"], valid, pids):
        rows = valid.shape[0]
        if quota < rows:
            # a hot bucket could overflow its tile and silently drop rows
            raise ValueError(
                f"ici shuffle quota {quota} < shard rows {rows}: a skewed "
                "bucket would overflow; size quota to the shard capacity")
        # rows ordered by target chip, each chip's in their original
        # order, dead rows last: the partition pass of the local plane
        perm, counts = partition_indices(
            jnp, jnp.where(valid, pids, n_dev).astype(jnp.int32), n_dev + 1)
        sent = counts[:n_dev]
        starts = jnp.cumsum(sent, dtype=jnp.int32) - sent
        got = jax.lax.all_to_all(sent, axis_name, 0, 0, tiled=True)
        offsets = jnp.cumsum(got, dtype=jnp.int32) - got
        rvalid = jnp.arange(n_dev * quota, dtype=jnp.int32) < jnp.sum(got)

        def route(a):
            tail = a.shape[1:]
            # room past the end: a block cut or written at its offset
            # never clamps
            ordered = jnp.concatenate(
                [a[perm], jnp.zeros((quota,) + tail, a.dtype)])
            tile = jnp.stack([
                jax.lax.dynamic_slice_in_dim(ordered, starts[t], quota)
                for t in range(n_dev)])
            recv = jax.lax.all_to_all(tile, axis_name, 0, 0, tiled=True)
            # block s holds ``got[s]`` rows and then rows meant for other
            # chips: the next block overwrites those, the mask the last
            buf = jnp.zeros(((n_dev + 1) * quota,) + tail, a.dtype)
            for s in range(n_dev):
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, recv[s], offsets[s], axis=0)
            live = rvalid.reshape((-1,) + (1,) * len(tail))
            return jnp.where(live, buf[:n_dev * quota],
                             jnp.zeros((), a.dtype))

        out = {k: route(a) for k, a in arrays.items()}
        return (out, rvalid, got) if with_counts else (out, rvalid)

    return exchange


def ici_hash_shuffle_step(mesh, axis_name: str, n_dev: int):
    """Builds the distributed query-shuffle step used by the multichip
    dryrun: local partial state -> hash-routed all_to_all -> merge.  This
    is the data-plane pattern every multi-chip exchange follows."""
    import jax
    import jax.numpy as jnp
    from ..ops.hashing import murmur3_long

    def route_targets(keys):
        h = murmur3_long(jnp, keys.astype(jnp.int64), jnp.uint32(42))
        t = h % np.int32(n_dev)
        return jnp.where(t < 0, t + n_dev, t).astype(jnp.int32)

    return route_targets
