"""Seconds XLA spent compiling before the window: what the persistent
cache did not answer."""


def read(run):
    return run["compile"]["setup"]["compile_seconds"]
