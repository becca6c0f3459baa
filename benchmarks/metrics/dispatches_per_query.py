"""Device programs dispatched through the kernel cache inside the window,
per collect (``kernel_cache.cache_stats()["dispatches"]``)."""


def read(run):
    delta = run["kernel_cache"].get("dispatches")
    if delta is None:
        return None
    return delta / run["window"]["collects"]
