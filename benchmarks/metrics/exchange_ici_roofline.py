"""Share of the ICI roofline at which the mesh exchange moves rows.

The least a collect's exchanges can take on a chip is the bytes that chip
has to send to the others over its ICI peak (``peaks_ici.json``).  The bytes
that change chips a collect (``mesh_bytes.py``: live rows bound for another
chip, at their width) are spread over the chips that worked; that time over
the device time of the ``jit_srt_MeshExchange_*`` programs a collect on the
busiest chip (``exchange_collective_ms``), which holds the tiles' cutting
and the packing of what was received besides the ``all_to_all`` itself.
Bandwidth-bound by construction: it counts no operation.  Nothing where no
exchange rode the plane; never 0."""

import mesh_bytes
import mesh_trace


def read(run):
    reduced = mesh_trace.for_run(run)
    seconds = mesh_trace.collective_s_per_collect(reduced)
    moved = mesh_bytes.cross_chip_bytes_per_collect(run)
    if seconds is None or moved is None:
        return None
    peak = mesh_bytes.ici_bytes_per_s(mesh_bytes.device_kind())
    least = moved / len(reduced["chips"]) / peak
    return 100.0 * least / seconds
