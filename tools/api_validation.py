"""API-drift validation — the analog of the reference's ``api_validation``
module (``ApiValidation.scala``: compares Gpu exec constructor signatures
against each Spark version's APIs so a shim mismatch is caught at build
time, not at runtime deep inside a query).

Two validations, both runnable standalone and from CI/tests:

1. **Engine contract** — every physical exec's constructor signature and
   every registered expression class is snapshotted into
   ``tools/generated_files/api_contract.json``; a later run against the
   contract reports removed/renamed classes and incompatible constructor
   changes (the drift the reference catches across its 14 shims).
2. **jax surface** — every jax API the shims/engine lean on is probed
   against the RUNNING jax version (the TPU build's version axis, SURVEY
   §2.11 TPU note), so a jaxlib upgrade that moves an entry point fails
   loudly here.

Usage:
    python tools/api_validation.py generate   # write the contract
    python tools/api_validation.py check      # validate against it
"""

from __future__ import annotations

import importlib
import inspect
import json
import os
import sys
from typing import Dict, List

CONTRACT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "generated_files", "api_contract.json")

_EXEC_MODULES = [
    "spark_rapids_tpu.sql.physical.basic",
    "spark_rapids_tpu.sql.physical.aggregate",
    "spark_rapids_tpu.sql.physical.join",
    "spark_rapids_tpu.sql.physical.sortlimit",
    "spark_rapids_tpu.sql.physical.window",
    "spark_rapids_tpu.sql.physical.exchange",
    "spark_rapids_tpu.sql.physical.transitions",
    "spark_rapids_tpu.sql.physical.generate",
    "spark_rapids_tpu.sql.physical.python_execs",
    "spark_rapids_tpu.sql.physical.fusion",
    "spark_rapids_tpu.sql.physical.dpp",
    "spark_rapids_tpu.io_.exec",
]

#: jax entry points the engine/shims rely on (probed, not imported lazily,
#: so a jax upgrade that moves one fails HERE with a clear message)
_JAX_SURFACE = [
    "jax.jit", "jax.device_get", "jax.device_put", "jax.tree.map",
    "jax.lax.sort", "jax.lax.while_loop", "jax.lax.scan",
    "jax.lax.associative_scan", "jax.lax.cond",
    "jax.sharding.Mesh", "jax.sharding.NamedSharding",
    "jax.sharding.PartitionSpec", "jax.experimental.shard_map.shard_map",
    "jax.block_until_ready", "jax.profiler.TraceAnnotation",
    "jax.nn.one_hot", "jax.numpy.argsort", "jax.numpy.cumsum",
]


def _exec_signatures() -> Dict[str, List[str]]:
    from spark_rapids_tpu.sql.physical.base import PhysicalPlan
    out: Dict[str, List[str]] = {}
    for mod_name in _EXEC_MODULES:
        mod = importlib.import_module(mod_name)
        for name, cls in vars(mod).items():
            if (inspect.isclass(cls) and issubclass(cls, PhysicalPlan)
                    and cls is not PhysicalPlan
                    and cls.__module__ == mod_name):
                try:
                    params = [p.name for p in
                              inspect.signature(cls.__init__).parameters
                              .values()][1:]  # drop self
                except (TypeError, ValueError):
                    params = []
                out[f"{mod_name}.{name}"] = params
    return out


def _expression_names() -> List[str]:
    from spark_rapids_tpu.sql.expressions.registry import EXPRESSION_REGISTRY
    return sorted(EXPRESSION_REGISTRY)


def _probe_jax_surface() -> List[str]:
    missing = []
    for path in _JAX_SURFACE:
        mod_path, attr = path.rsplit(".", 1)
        try:
            obj = importlib.import_module(mod_path)
        except ImportError:
            # dotted attribute chains (jax.tree.map)
            parts = path.split(".")
            try:
                obj = importlib.import_module(parts[0])
                for p in parts[1:-1]:
                    obj = getattr(obj, p)
                attr = parts[-1]
            except (ImportError, AttributeError):
                missing.append(path)
                continue
        if not hasattr(obj, attr):
            missing.append(path)
    return missing


def generate() -> dict:
    contract = {
        "execs": _exec_signatures(),
        "expressions": _expression_names(),
    }
    os.makedirs(os.path.dirname(CONTRACT), exist_ok=True)
    with open(CONTRACT, "w") as fh:
        json.dump(contract, fh, indent=1, sort_keys=True)
    return contract


def check() -> List[str]:
    """Returns a list of drift findings (empty = clean)."""
    problems: List[str] = []
    missing_jax = _probe_jax_surface()
    for p in missing_jax:
        problems.append(f"jax surface: {p} is gone in the running jax "
                        f"(add a shim provider)")
    if not os.path.exists(CONTRACT):
        problems.append(f"contract file missing: {CONTRACT} "
                        f"(run `generate` first)")
        return problems
    with open(CONTRACT) as fh:
        contract = json.load(fh)
    now_execs = _exec_signatures()
    for name, params in contract["execs"].items():
        if name not in now_execs:
            problems.append(f"exec removed/renamed: {name}")
        else:
            got = now_execs[name]
            # removing or reordering existing positional params breaks
            # callers; appending new defaulted params is fine
            if got[:len(params)] != params:
                problems.append(
                    f"exec constructor changed incompatibly: {name} "
                    f"{params} -> {got}")
    now_exprs = set(_expression_names())
    for e in contract["expressions"]:
        if e not in now_exprs:
            problems.append(f"expression unregistered: {e}")
    return problems


def main() -> int:
    cmd = sys.argv[1] if len(sys.argv) > 1 else "check"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if cmd == "generate":
        c = generate()
        print(f"wrote {CONTRACT}: {len(c['execs'])} execs, "
              f"{len(c['expressions'])} expressions")
        return 0
    problems = check()
    for p in problems:
        print(f"DRIFT: {p}")
    print(f"{len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
