"""Set-up, warm-up (`Server.warmup`, then the query sketch and the
scheduler path at the cell's shapes): host clock, in s."""


def read(rec):
    return rec["setup"]["warmup_s"]
