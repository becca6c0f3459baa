"""Pallas TPU kernels for hot ops (SURVEY §2.10: real device kernels, not
Python stand-ins).  First resident: Spark-exact murmur3 over int64 keys —
the inner loop of every hash partitioning/shuffle route.  The kernel does
the 32-bit mixing on the VPU over (block, 128) tiles; int64 inputs are
split into uint32 halves outside (TPU int64 vector support is emulated).

Dispatch: ``hashing.murmur3_long`` uses the Pallas kernel on the TPU
backend and the plain jnp implementation elsewhere; ``interpret=True`` is
for the CPU tests only — results are bit-identical across all three.
"""

from __future__ import annotations

from functools import partial

import numpy as np

_LANES = 128
_BLOCK_ROWS = 256
#: BlockSpec index maps must return 32-bit block indices: the package runs
#: with jax_enable_x64, where a Python ``0`` traces as i64 and Mosaic then
#: refuses the index map's ``func.return`` (i32, i64).
_Z = np.int32(0)


def _mix_ops():
    # np.uint32 python scalars: weak-typed constants baked into the trace
    # (jnp scalars would be captured device consts, which pallas rejects)
    C1 = np.uint32(0xcc9e2d51)
    C2 = np.uint32(0x1b873593)
    M5 = np.uint32(0xe6546b64)

    def rotl(x, r):
        return (x << np.uint32(r)) | (x >> np.uint32(32 - r))

    def mix_k1(k1):
        return rotl(k1 * C1, 15) * C2

    def mix_h1(h1, k1):
        return rotl(h1 ^ k1, 13) * np.uint32(5) + M5

    def fmix(h1, length):
        h1 = h1 ^ np.uint32(length)
        h1 = h1 ^ (h1 >> np.uint32(16))
        h1 = h1 * np.uint32(0x85ebca6b)
        h1 = h1 ^ (h1 >> np.uint32(13))
        h1 = h1 * np.uint32(0xc2b2ae35)
        return h1 ^ (h1 >> np.uint32(16))

    return mix_k1, mix_h1, fmix


def _murmur3_kernel():
    import jax.numpy as jnp

    mix_k1, mix_h1, fmix = _mix_ops()

    def kernel(low_ref, high_ref, seed_ref, out_ref):
        low = low_ref[:]
        high = high_ref[:]
        h1 = mix_h1(seed_ref[:], mix_k1(low))
        h1 = mix_h1(h1, mix_k1(high))
        out_ref[:] = fmix(h1, 8).astype(jnp.int32)

    return kernel


def murmur3_long_pallas(vals_i64, seed, interpret: bool = False):
    """int64[n] -> int32[n] Spark murmur3 as a Pallas TPU program.
    ``seed`` may be a scalar or a per-row uint32 array (the multi-column
    hash chains per-row seeds)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = vals_i64.shape[0]
    low = vals_i64.astype(jnp.uint32)
    high = (vals_i64.astype(jnp.uint64) >> np.uint64(32)).astype(jnp.uint32)
    seed_arr = jnp.broadcast_to(
        jnp.asarray(seed, dtype=jnp.uint32), (n,))

    rows = -(-n // _LANES)
    block = min(_BLOCK_ROWS, max(8, rows))
    padded_rows = -(-rows // block) * block
    pad = padded_rows * _LANES - n

    def fold(a):
        return jnp.pad(a, (0, pad)).reshape(padded_rows, _LANES)

    grid = padded_rows // block
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, _Z))
    out = pl.pallas_call(
        _murmur3_kernel(),
        grid=(grid,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANES), jnp.int32),
        interpret=interpret,
    )(fold(low), fold(high), fold(seed_arr))
    return out.reshape(-1)[:n]


def _seg_sum_kernel(out_groups: int):
    """Grid-accumulating MXU kernel: per block, build the (block*lanes,
    OUT) one-hot of the group ranks and reduce all slots with ONE matmul
    — the segmented-sum hot loop of the fused aggregate expressed as an
    explicit systolic-array program (TPU grids run sequentially, so
    ``out_ref += ...`` accumulates across blocks)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(v_ref, r_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        v = v_ref[...]                      # (s, block, lanes)
        r = r_ref[...]                      # (block, lanes)
        onehot = (r[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, out_groups), 2)).astype(jnp.float32)
        flat_v = v.reshape(v.shape[0], -1)           # (s, block*lanes)
        flat_o = onehot.reshape(-1, out_groups)      # (block*lanes, OUT)
        out_ref[...] += jax.lax.dot(
            flat_v, flat_o,
            preferred_element_type=jnp.float32)      # (s, OUT) on the MXU

    return kernel


def seg_sum_f32_pallas(values, rank, out_size: int,
                       interpret: bool = False):
    """float32[s, n] slot values + int32[n] group ranks -> float32[s,
    out_size] per-group sums as a Pallas TPU program (rank >= out_size
    contributes nothing — the dead-row convention of groupby_reduce).
    Accumulation order is block-major, the same error class as the
    engine's one-hot-matmul reduction path."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    s, n = values.shape
    OUT = -(-int(out_size) // _LANES) * _LANES  # lane-pad the group dim
    rows = -(-n // _LANES)
    block = min(64, max(8, rows))
    padded_rows = -(-rows // block) * block
    pad = padded_rows * _LANES - n
    v = jnp.pad(values, ((0, 0), (0, pad))).reshape(s, padded_rows, _LANES)
    # pad ranks with OUT (out of range -> all-false one-hot)
    r = jnp.pad(rank.astype(jnp.int32), (0, pad),
                constant_values=OUT).reshape(padded_rows, _LANES)
    r = jnp.where(r < int(out_size), r, OUT)  # oversize ranks drop too

    grid = padded_rows // block
    out = pl.pallas_call(
        _seg_sum_kernel(OUT),
        grid=(grid,),
        in_specs=[pl.BlockSpec((s, block, _LANES), lambda i: (_Z, i, _Z)),
                  pl.BlockSpec((block, _LANES), lambda i: (i, _Z))],
        out_specs=pl.BlockSpec((s, OUT), lambda i: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((s, OUT), jnp.float32),
        interpret=interpret,
    )(v, r)
    return out[:, :int(out_size)]


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


#: kernel name -> True once its probe has passed on the TPU backend
_PROBE_OK: dict = {}


def _probe(name: str, check) -> bool:
    """Is the Pallas kernel ``name`` the path to take on this backend?

    Off the TPU: False, without trying — the plain jnp implementation is
    the path there (interpret mode exists for the CPU tests only; nothing
    selects it at run time).  On the TPU: a one-time end-to-end probe —
    compile + execute + verify a known answer — whose failure RAISES.
    A kernel the chip's compiler refuses, or that answers wrongly, is a
    defect to repair, not a reason to give way to jnp in silence.

    Call sites sit inside jitted stages, so the probe runs under
    ``jax.core.eval_context``: its arrays are concrete and its jitted
    ``pallas_call`` compiles and executes for real even while an outer
    trace is being staged."""
    if not on_tpu():
        return False
    if not _PROBE_OK.get(name):
        import jax
        try:
            with jax.core.eval_context():
                ok = bool(check())
        except Exception as e:
            raise RuntimeError(
                f"Pallas kernel {name!r} failed its probe on the TPU "
                f"backend: {type(e).__name__}: {e}") from e
        if not ok:
            raise RuntimeError(
                f"Pallas kernel {name!r} compiled on the TPU backend but "
                f"returned a wrong answer in its probe")
        _PROBE_OK[name] = True
    return True


def murmur3_available() -> bool:
    def check():
        import jax
        import jax.numpy as jnp
        vals = np.asarray([0, 1, -1, 2**62, -(2**62)], np.int64)
        got = np.asarray(jax.jit(
            lambda v: murmur3_long_pallas(v, np.uint32(42)))(
                jnp.asarray(vals)))
        from .hashing import murmur3_long as _murmur3
        return np.array_equal(got, _murmur3(np, vals, np.uint32(42)))
    return _probe("murmur3", check)


def seg_sum_available() -> bool:
    def check():
        import jax
        import jax.numpy as jnp
        out = np.asarray(jax.jit(
            lambda v, r: seg_sum_f32_pallas(v, r, 8))(
                jnp.ones((1, 300), jnp.float32), jnp.zeros(300, jnp.int32)))
        return abs(float(out[0, 0]) - 300.0) < 1e-3
    return _probe("seg_sum", check)
