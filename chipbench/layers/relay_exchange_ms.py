"""Device self time under the relay's program scope ``relay.exchange``
per walk batch, averaged over the chips, in ms: the walker-record and
path-record ``all_to_all`` mailboxes and their packing, each round
(``chipbench/relay_trace.py``)."""

from pathlib import Path

from chipbench import relay_trace


def read(f):
    return relay_trace.under_ms(f, Path(__file__).parents[2],
                                "relay.exchange")
