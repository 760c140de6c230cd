"""Device self time under the relay's program scope ``relay.segment``
per walk batch, averaged over the chips, in ms: each round's segment
megakernel (``walk_segment`` under ``walk.kernel``) and the walk wrapper's
``walk.relayout``, which nest inside it (``chipbench/relay_trace.py``)."""

from pathlib import Path

from chipbench import relay_trace


def read(f):
    return relay_trace.under_ms(f, Path(__file__).parents[2],
                                "relay.segment")
