"""Row-group runs of the parquet file whose device decode the program gave
up (``parquetDecodeFilesDeclined`` of ``sess.last_query_metrics``), summed
over one collect of each of the cell's queries.

What the counter counts, from ``io_/exec.py`` and ``io_/device_parquet.py``
(read in PR 25): the device decoder takes a run column by column; a string
column whose padded matrix would pass ``spark.rapids.sql.strings.
raggedSplitBytes`` makes it drop the whole run (reason ``ragged-strings``),
the columns it had already decoded on the device are thrown away, and the
host reads the run again with pyarrow and uploads it.  So a declined run
costs a device decode *and* a host decode; 0 means every run stayed on the
device.  Nothing to read in a cell that scans no file."""


def read(run):
    seen = [m["parquetDecodeFilesDeclined"]
            for m in run["query_metrics"].values()
            if "parquetDecodeFilesDeclined" in m
            or "parquetDecodeFilesEngaged" in m]
    if not seen:
        return None
    return float(sum(seen))
