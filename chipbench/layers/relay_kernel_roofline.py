"""Share of one chip's walk-kernel time that its share of the least HBM
traffic of an O(1) Bingo draw would take at one chip's peak bandwidth,
in %.

As ``walk_kernel_roofline`` counts it, per hop taken (in the returned
paths): the vertex's inter-group alias row (a probability and a
redirect per entry), its degree, and the picked slot's neighbour id and
bias; the count is the algorithm's, the same whatever implements the
walk.  On a mesh each chip walks the hops from its own vertices, so a
chip's share is the hops over the cell's chips, and its time is its
own custom calls (the segment megakernel), averaged over the chips."""


def read(f):
    c = f["counters"]
    devs = f["trace"]["devices"].values()
    ns = sum(d["custom_call_ns"] for d in devs) / max(len(devs), 1)
    if not ns or not c.get("hops"):
        return None
    per_hop = 8 * c["alias_entries"] + 4 + 8
    least_s = c["hops"] / f["chips"] * per_hop / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
