"""Set-up, ingest (`engine/lifecycle.LiveIndex.append`, `compact`): host
clock around generating the lake and indexing it, in s."""


def read(rec):
    return rec["setup"]["ingest_s"]
