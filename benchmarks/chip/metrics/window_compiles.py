"""window_compiles: executables asked of the compiler or the persistent
cache while the window's profile ran (the program's compile counter,
``repro.runtime.compile_cache``); nothing may compile there, so it
reads 0."""

from harness import compiles


def read(ctx, facts, trace):
    snap = compiles.reading(ctx)
    if snap is None:
        return None
    return snap["profiled"]["compile_requests"]
