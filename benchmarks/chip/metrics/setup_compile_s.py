"""setup_compile_s: seconds of set-up spent tracing, lowering and
getting executables (compiling, or loading from the persistent cache),
from process start to the window: the program's compile counter
(``repro.runtime.compile_cache``) less what fired in the window."""

import sys

from harness import compiles


def read(ctx, facts, trace):
    snap = compiles.reading(ctx)
    if snap is None:
        return None
    part = compiles.setup_part(snap)
    print("[compiles] set-up: " + ", ".join(
        f"{k} {v!r}" for k, v in part.items()), file=sys.stderr)
    return compiles.setup_compile_s(snap)
