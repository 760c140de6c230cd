"""Relay rounds per walk batch: launches (custom calls) of the segment
megakernel ``walk_segment`` per chip in the traced window, over the
batches fetched.  The relay launches it once a round on every chip
(``repro/distributed/relay.py``), so this is the rounds the engine
counts (``DynamicWalkEngine.relay_stats()``)."""

from pathlib import Path

from chipbench import relay_trace


def read(f):
    return relay_trace.kernel_launches(f, Path(__file__).parents[2],
                                       "walk_segment")
