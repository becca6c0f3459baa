"""Device milliseconds a traced collect spends in the mesh exchange's
programs (``jit_srt_MeshExchange_*``: tiles cut, ``all_to_all`` over ICI,
received pieces packed) on the chip that spent most in them
(``mesh_trace.py``).  Nothing where fewer than two chips worked or no such
program ran."""

import mesh_trace


def read(run):
    seconds = mesh_trace.collective_s_per_collect(mesh_trace.for_run(run))
    return None if seconds is None else 1e3 * seconds
