"""Device self time under the program scope ``walk.relayout`` per walk
batch, in ms: the 128-lane meta rows and ``(V, 1, W)`` row tables the
walk wrapper builds for each call, and any layout copy of the kernel's
output (``chipbench/scopes.py``)."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.scope_ms(f, Path(__file__).parents[2], "walk.relayout",
                           "batches")
