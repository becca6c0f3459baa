"""Milliseconds per collect that jax spent tracing, lowering and compiling
(or loading from the compile cache) inside the window:
``kernel_cache.cache_stats()["retrace_ms"]``, the sum of jax's own
``jaxpr_trace`` (outermost), ``jaxpr_to_mlir_module`` and
``backend_compile`` durations.  0 is the steady state."""


def read(run):
    delta = run["kernel_cache"].get("retrace_ms")
    if delta is None or not run["trace"]:
        return None
    return delta / run["window"]["collects"]
