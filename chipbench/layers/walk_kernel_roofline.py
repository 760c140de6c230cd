"""Share of the walk megakernel's time that the least HBM traffic of
an O(1) Bingo draw would take at the chip's peak bandwidth, in %.

Per hop taken (counted in the returned paths): the vertex's inter-group
alias row (a probability and a redirect per entry), its degree, and the
picked slot's neighbour id and bias.  The count is the algorithm's, the
same whatever implements the walk."""


def read(f):
    c = f["counters"]
    devs = f["trace"]["devices"].values()
    ns = sum(d["custom_call_ns"] for d in devs) / max(len(devs), 1)
    if not ns or not c.get("hops"):
        return None
    per_hop = 8 * c["alias_entries"] + 4 + 8
    least_s = c["hops"] * per_hop / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
