"""Schedule interpreter: blocking device->host syncs per request, from the
session's ``SyncCounter`` (syncs/query)."""


def read(run):
    syncs = [r.syncs for r in run.requests if r.syncs is not None]
    if not syncs:
        return None
    return sum(syncs) / len(syncs)
