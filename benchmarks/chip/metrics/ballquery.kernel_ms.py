"""Device time of the batched ball-query kernel (``ball_query``) per
planning call: its events' summed durations in the traced window over the
calls traced."""


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    if not t or not calls or not t["kernel_events"].get("ball_query"):
        return None
    return t["kernel_s"]["ball_query"] / len(calls) * 1e3
