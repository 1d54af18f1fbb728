"""Host time of one engine call (plan, executor, copies, syncs): its wall
time less the device's busy time inside it, averaged over the traced
calls."""


def read(run):
    calls = ((run.trace or {}).get("spans") or {}).get("bench.execute")
    if not calls:
        return None
    return sum((e - s) - busy for s, e, busy in calls) / len(calls) * 1e3
