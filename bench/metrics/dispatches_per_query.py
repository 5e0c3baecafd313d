"""Server (`engine/serve.Server`, `_SegmentExec`): device dispatches per
query over the window (`throughput()` deltas of ``device_dispatches`` and
``queries``). A survivor-rung overflow's second dispatch shows here."""


def read(rec):
    d = rec["server"]
    return d["device_dispatches"] / d["queries"] if d["queries"] else None
