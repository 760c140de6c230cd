"""Device time of the update megakernel per update round, in ms: the
custom calls (Mosaic kernels) of the window's update programs, over the
rounds ingested in the window."""


def read(f):
    devs = f["trace"]["devices"].values()
    if not sum(d["custom_calls"] for d in devs) or not f["counters"].get(
            "rounds"):
        return None
    ns = sum(d["custom_call_ns"] for d in devs) / len(devs)
    return ns / 1e6 / f["counters"]["rounds"]
