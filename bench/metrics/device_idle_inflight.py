"""Device: the idle share over only the time in which at least one request
was outstanding (due or sent, and not yet answered)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["inflight_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 1.0 - t["busy_inflight_s"] / t["inflight_s"]
