"""Device time of the guard's classifier program per update round, in
ms (programs named ``jit_classify`` in the trace)."""


def read(f):
    devs = list(f["trace"]["devices"].values())
    ns = sum(d["programs_ns"].get("jit_classify", 0) for d in devs)
    if not ns or not f["counters"].get("rounds"):
        return None
    return ns / len(devs) / 1e6 / f["counters"]["rounds"]
