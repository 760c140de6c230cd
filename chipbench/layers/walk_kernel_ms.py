"""Device time of the walk megakernel per walk batch, in ms: the custom
calls (Mosaic kernels) of the window's walk programs, averaged over the
chips, over the batches the window fetched."""


def read(f):
    devs = f["trace"]["devices"].values()
    calls = sum(d["custom_calls"] for d in devs)
    if not calls or not f["counters"].get("batches"):
        return None
    ns = sum(d["custom_call_ns"] for d in devs) / len(devs)
    return ns / 1e6 / f["counters"]["batches"]
