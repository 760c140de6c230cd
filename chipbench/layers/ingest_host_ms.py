"""Mean host time of the program span ``engine.ingest`` in the traced
window, less the dispatch waits inside it, in ms: the engine's own host
work for one ``DynamicWalkEngine.ingest`` call (``repro/serve/spans.py``,
read from the trace by ``chipbench/scopes.py``).  When the device is the
bottleneck a launch in the call waits for a free in-flight slot (PJRT's
back-pressure); that wait is the device's time, not the engine's, and
is left out."""

from pathlib import Path

from chipbench import scopes


def read(f):
    return scopes.span_ms(f, Path(__file__).parents[2], "engine.ingest")
