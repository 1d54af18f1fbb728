"""Device time of the batched FPS kernel (``fps_sample``) per planning
call: its events' summed durations in the traced window over the calls
traced."""


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    if not t or not calls or not t["kernel_events"].get("fps_sample"):
        return None
    return t["kernel_s"]["fps_sample"] / len(calls) * 1e3
