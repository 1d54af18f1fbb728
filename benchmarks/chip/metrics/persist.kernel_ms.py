"""Device time of the persistent megakernel per call: the summed durations
of its events in the traced window over the calls traced."""


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    if not t or not calls or not t["kernel_events"].get("persist"):
        return None
    return t["kernel_s"]["persist"] / len(calls) * 1e3
