"""Mean host time of one scheduling quantum, ``tick()`` and the
``poll()`` after it, in ms (the benchmark's own spans)."""


def read(f):
    n, s = f["host"].get("cb.tick", (0, 0.0))
    _, p = f["host"].get("cb.poll", (0, 0.0))
    return 1e3 * (s + p) / n if n else None
