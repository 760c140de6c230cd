"""What the relay's readers take from a traced run: device self time
under a scope component per batch, and a Pallas kernel's launches.

The relay (``repro/distributed/relay.py``) runs as one program a batch
on every chip, a ``while`` loop of rounds whose body holds the scopes
``relay.segment`` (the segment megakernel with the walk wrapper's
``walk.kernel`` and ``walk.relayout`` nested in it), ``relay.exchange``
and ``relay.merge``.  Times come from ``scopes.py``'s ``under_s``, so a
nested scope counts in the scope that holds it; launches are the
custom calls (Mosaic kernels) whose HLO instruction carries the
kernel's ``name``.  XLA gives the layout copies of a kernel's outputs
the kernel's name stack too, so a launch is not an operation whose name
stack ends in the name.  Both are averaged over the chips.
"""

from __future__ import annotations

import functools
from pathlib import Path

from chipbench import scopes, xplane


def under_ms(facts: dict, root: Path, comp: str, per: str = "batches"):
    """Device self time under scope component ``comp`` per unit of the
    run's counter ``per``, in ms, or None where the trace has none."""
    red = scopes.of_run(facts, root)
    n = facts["counters"].get(per)
    if red is None or not n or not red["under_s"].get(comp):
        return None
    return 1e3 * red["under_s"][comp] / n


def launches(spans, devices, kernel: str) -> float:
    """Launches of the kernel named ``kernel`` that start inside the
    ``cb.window`` span, per device, from ``xplane.load_events``'s
    ``(spans, devices)``: custom-call operations whose instruction is
    named ``kernel``."""
    win = [(s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no cb.window span")
    t0, t1 = min(s for s, _ in win), max(e for _, e in win)
    n = sum(1 for dev in devices.values() for name, s, _, _ in dev["ops"]
            if t0 <= s < t1 and xplane._op(name) == (kernel, "custom-call"))
    return n / max(len(devices), 1)


@functools.lru_cache(maxsize=2)
def _launches_file(path: str, mtime_ns: int, kernel: str) -> float:
    return launches(*xplane.load_events(path), kernel)


def kernel_launches(facts: dict, root: Path, kernel: str,
                    per: str = "batches"):
    """Launches of ``kernel`` per chip per unit of the run's counter
    ``per``, from the trace ``scopes.of_run`` accepts, or None."""
    n = facts["counters"].get(per)
    if not n or scopes.of_run(facts, root) is None:
        return None
    path = sorted(Path(root).glob(".chipbench_trace/*/**/*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime_ns)[-1]
    got = _launches_file(str(path), path.stat().st_mtime_ns, kernel)
    return got / n if got else None
