"""Device self time under the relay's program scope ``relay.merge``
per walk batch, averaged over the chips, in ms: the free-list
placement of queued walkers into slots, the arrivals' merge into the
waiting queue and the home blocks, and the pending-count ``psum``, each
round, on the overlapped schedule the engine runs (the bulk one
nests its exchange inside the merge) (``chipbench/relay_trace.py``)."""

from pathlib import Path

from chipbench import relay_trace


def read(f):
    return relay_trace.under_ms(f, Path(__file__).parents[2],
                                "relay.merge")
