"""Mean host time of the program span ``engine.walk`` in the traced
window, less the dispatch waits inside it, in ms: the engine's own host
work for one ``DynamicWalkEngine.walk`` call (``repro/serve/spans.py``,
read from the trace by ``chipbench/scopes.py``; see
``ingest_host_ms``)."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.span_ms(f, Path(__file__).parents[2], "engine.walk")
