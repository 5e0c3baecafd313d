"""Scheduler (`engine/scheduler.AsyncScheduler`): queries completed per
dispatch group over the window (`stats()` deltas)."""


def read(rec):
    d = rec["scheduler"]
    return d["completed"] / d["batches"] if d["batches"] else None
