"""Device self time under the program scope ``update.epilogue`` per
update round, in ms: the affected vertices' alias rows (``alias_rows``)
and their counters written back with the round's stats
(``writeback``), from ``chipbench/scopes.py``."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.scope_ms(f, Path(__file__).parents[2], "update.epilogue",
                           "rounds")
