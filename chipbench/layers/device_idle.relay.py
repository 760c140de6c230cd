"""Share of the traced window in which no operation ran on the chip
(averaged over the chips), in %."""


def read(f):
    t = f["trace"]
    if not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
