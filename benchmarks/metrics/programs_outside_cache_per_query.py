"""Device program runs per traced collect whose module name lacks the
kernel cache's ``jit_srt_`` prefix: ``jnp`` calls in an exec's Python, the
decoders' own programs, keyless ``jax.jit``.  ``dispatches_per_query``
counts the launches that go through the cache; these are the ones it does
not see.  ``program_spans.py`` prints the total and the names."""

import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None:
        return None
    return reduced["program_runs_outside_cache"] / reduced["collects"]
