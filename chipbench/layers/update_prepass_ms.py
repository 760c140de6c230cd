"""Device self time under the program scope ``update.prepass`` per
update round, in ms: the update wrapper's ordering prepass (sorts,
``searchsorted``, the insert and delete patch scatters), from the raw
trace's name stacks (``chipbench/scopes.py``)."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.scope_ms(f, Path(__file__).parents[2], "update.prepass",
                           "rounds")
