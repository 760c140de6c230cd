"""Share of the update megakernel's time that a round's least HBM
traffic would take at the chip's peak bandwidth, in %.

The least bytes come from the lanes and the graph, not the kernel's
shapes (``drivers/ingest.py``): each affected vertex's live row read and
written once, its degree, group counters and alias row."""


def read(f):
    c = f["counters"]
    devs = f["trace"]["devices"].values()
    ns = sum(d["custom_call_ns"] for d in devs) / max(len(devs), 1)
    if not ns or not c.get("update_least_bytes"):
        return None
    least_s = c["update_least_bytes"] / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
