"""Collective time per walk batch during which no other operation ran
on the chip, averaged over the chips, in ms: the part of the relay's
``all_to_all`` mailboxes and ``psum`` termination test that the
overlapped schedule does not hide behind the segment megakernel
(``xplane.reduce_trace``'s ``exposed_collective_ns``)."""


def read(f):
    devs = list(f["trace"]["devices"].values())
    n = f["counters"].get("batches")
    if not n or not sum(d["collective_ns"] for d in devs):
        return None
    ns = sum(d["exposed_collective_ns"] for d in devs) / len(devs)
    return ns / 1e6 / n
