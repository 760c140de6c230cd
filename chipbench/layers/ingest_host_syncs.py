"""The engine's device-to-host reads per update round: the program
spans ``engine.read`` (one per read) in the traced window, over the
rounds.  On the deferred ingest path they are the guard backlog's reads
at each ``drain_guard``: five lane arrays and the delete count of each
round (``repro/serve/spans.py``, ``chipbench/scopes.py``).  Each read
is a point where the host may wait for the device, so the count, not
the reads' time (mostly that wait), is what the engine controls."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.span_count(f, Path(__file__).parents[2], "engine.read",
                             "rounds")
