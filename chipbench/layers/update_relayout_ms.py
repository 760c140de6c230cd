"""Device self time under the program scope ``update.relayout`` per
update round, in ms: the row tables re-laid as ``(V, 1, C)`` for the
update kernel, and XLA's layout copies of the kernel's ``(V, 1, C)``
outputs back to ``(V, C)``, which carry no name of their own and are
given the scope the program puts around that reshape
(``chipbench/scopes.py``)."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.scope_ms(f, Path(__file__).parents[2], "update.relayout",
                           "rounds")
