"""Format registry + schema inference for file sources (reference scan
framework SURVEY §2.5).  Host decode is pyarrow (the CPU-side parse the
reference does before device upload); the TPU gets one upload per batch."""

from __future__ import annotations

import glob as _glob
import os
from typing import Dict, List, Optional, Sequence

import pyarrow as pa

from .. import types as T


def expand_paths(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        elif os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if not f.startswith((".", "_")):
                        out.append(os.path.join(root, f))
        else:
            out.append(p)
    return out


def _normalize_fmt(fmt: str, options: Dict) -> str:
    """hive text tables are ^A-delimited headerless csv (reference
    GpuHiveTextFileFormat, org/apache/spark/sql/hive/rapids)."""
    if fmt in ("hivetext", "hive-text", "hive"):
        options.setdefault("sep", "\x01")
        options.setdefault("header", "false")
        return "csv"
    return fmt


def infer_schema(fmt: str, paths: Sequence[str], options: Dict) -> T.StructType:
    fmt = _normalize_fmt(fmt, options)
    files = expand_paths(paths)
    if not files:
        raise FileNotFoundError(f"no input files for {paths}")
    f0 = files[0]
    if fmt == "parquet":
        import pyarrow.parquet as pq
        schema = pq.read_schema(f0)
    elif fmt == "orc":
        import pyarrow.orc as orc
        schema = orc.ORCFile(f0).schema
    elif fmt == "csv":
        table = read_file(fmt, f0, options, head_rows=1000)
        schema = table.schema
    elif fmt == "json":
        table = read_file(fmt, f0, options, head_rows=1000)
        schema = table.schema
    elif fmt == "avro":
        from .avro_reader import avro_schema
        return avro_schema(f0)
    else:
        raise ValueError(f"unknown format {fmt}")
    return T.StructType(tuple(
        T.StructField(f.name, T.from_arrow(f.type), f.nullable)
        for f in schema))


def schema_names(fmt: str, path: str) -> List[str]:
    """A parquet or ORC file's own column names, from its footer."""
    if fmt == "parquet":
        import pyarrow.parquet as pq
        return pq.read_schema(path).names
    import pyarrow.orc as orc
    return orc.ORCFile(path).schema.names


def read_csv_source(src, options: Dict,
                    columns: Optional[List[str]] = None) -> pa.Table:
    """CSV parse over a path OR a file-like source (the device decoder's
    decline path re-parses the bytes it already read)."""
    import pyarrow.csv as pcsv
    has_header = str(options.get("header", "true")).lower() == "true"
    sep = options.get("sep", options.get("delimiter", ","))
    read_opts = pcsv.ReadOptions(
        autogenerate_column_names=not has_header)
    parse_opts = pcsv.ParseOptions(delimiter=sep)
    convert = pcsv.ConvertOptions(
        null_values=[options.get("nullValue", "")],
        strings_can_be_null=True)
    t = pcsv.read_csv(src, read_options=read_opts,
                      parse_options=parse_opts, convert_options=convert)
    if not has_header:
        t = t.rename_columns([f"_c{i}" for i in range(t.num_columns)])
    if columns:
        t = t.select(columns)
    return t


def read_json_source(src, options: Dict,
                     columns: Optional[List[str]] = None) -> pa.Table:
    """JSON-lines parse over a path OR a file-like source (the device
    decoder's decline path re-parses the bytes it already read)."""
    import pyarrow.json as pjson
    t = pjson.read_json(src)
    if columns:
        t = t.select(columns)
    return t


def read_file(fmt: str, path: str, options: Dict,
              columns: Optional[List[str]] = None,
              head_rows: Optional[int] = None) -> pa.Table:
    fmt = _normalize_fmt(fmt, options)
    if fmt == "parquet":
        import pyarrow.parquet as pq
        return pq.read_table(path, columns=columns)
    if fmt == "orc":
        import pyarrow.orc as orc
        return orc.ORCFile(path).read(columns=columns)
    if fmt == "csv":
        return read_csv_source(path, options, columns)
    if fmt == "json":
        return read_json_source(path, options, columns)
    if fmt == "avro":
        from .avro_reader import read_avro
        t = read_avro(path)
        if columns:
            t = t.select(columns)
        return t
    raise ValueError(f"unknown format {fmt}")
