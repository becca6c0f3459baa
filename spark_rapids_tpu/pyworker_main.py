"""Python UDF worker process — the reference's ``python/rapids/daemon.py``
worker analog.

Launched BY FILE PATH (``python .../pyworker_main.py``), never imported:
the worker must not import ``spark_rapids_tpu`` (whose init configures
jax and could claim the chip, which belongs to ONE process) — it needs only pandas/pyarrow/
cloudpickle.

Protocol (length-prefixed frames over the stdio pipes; all lengths are
little-endian uint64):

  parent -> worker, per job:
      [len][cloudpickle(job_fn)] [ntables] ([len][arrow IPC stream])*
  worker -> parent:
      [status u8]  0: [ntables] ([len][arrow IPC stream])*
                   1: [len][utf-8 traceback]
                      [len][cloudpickle(exception) or 0 bytes]

``job_fn(list[pd.DataFrame]) -> list[pd.DataFrame]`` carries the user
function AND the exec's shape logic (map-iterator, per-group, pairs) as
one picklable closure, so this worker stays a dumb executor.

stdout is re-pointed at stderr before the loop so user ``print`` cannot
corrupt the frame stream; the protocol writes to a private dup of the
original stdout fd.
"""

import os
import struct
import sys
import traceback


def _read_exact(f, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError
        buf += chunk
    return buf


def main() -> None:
    proto_in = os.fdopen(os.dup(0), "rb", buffering=0)
    proto_out = os.fdopen(os.dup(1), "wb", buffering=0)
    # user print() -> stderr; reading stdin in user code hits EOF
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    os.dup2(2, 1)

    import cloudpickle
    import pyarrow as pa

    def read_table() -> pa.Table:
        (n,) = struct.unpack("<Q", _read_exact(proto_in, 8))
        with pa.ipc.open_stream(pa.BufferReader(
                _read_exact(proto_in, n))) as rd:
            return rd.read_all()

    while True:
        try:
            head = proto_in.read(8)
        except Exception:
            break
        if not head or len(head) < 8:
            break  # parent closed the pipe: clean shutdown
        (n,) = struct.unpack("<Q", head)
        job_blob = _read_exact(proto_in, n)
        (k,) = struct.unpack("<Q", _read_exact(proto_in, 8))
        tables = [read_table() for _ in range(k)]
        try:
            # unpickle INSIDE the job try: a closure that fails to
            # deserialize (missing module in the worker) must report as
            # a typed error, not kill the worker and masquerade as an
            # interpreter crash
            job_fn = cloudpickle.loads(job_blob)
            pdfs = [t.to_pandas() for t in tables]
            outs = job_fn(pdfs)
            # serialize EVERYTHING before the status byte: a failure
            # after status 0 would corrupt the frame stream and hang
            # the parent mid-read
            blobs = []
            for o in outs:
                t = o if isinstance(o, pa.Table) \
                    else pa.Table.from_pandas(o, preserve_index=False)
                sink = pa.BufferOutputStream()
                with pa.ipc.new_stream(sink, t.schema) as wr:
                    wr.write_table(t)
                blobs.append(sink.getvalue().to_pybytes())
        except BaseException as e:
            tb = traceback.format_exc().encode("utf-8")
            try:
                exc_blob = cloudpickle.dumps(e)
            except Exception:
                exc_blob = b""
            try:
                proto_out.write(b"\x01")
                proto_out.write(struct.pack("<Q", len(tb)))
                proto_out.write(tb)
                proto_out.write(struct.pack("<Q", len(exc_blob)))
                proto_out.write(exc_blob)
            except Exception:
                os._exit(13)  # cannot report: die, parent sees a crash
            continue
        try:
            proto_out.write(b"\x00")
            proto_out.write(struct.pack("<Q", len(blobs)))
            for b in blobs:
                proto_out.write(struct.pack("<Q", len(b)))
                proto_out.write(b)
        except Exception:
            os._exit(13)  # mid-stream write failure: never half-frame


if __name__ == "__main__":
    main()
