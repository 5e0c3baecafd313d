"""Device: share of the traced window in which no operation ran on the
device (1 − union of the operations' intervals / window)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
