"""95th percentile of how late the load generator submitted requests
after their due time, in ms."""


def read(f):
    return f["counters"].get("gen_lag_p95_ms")
