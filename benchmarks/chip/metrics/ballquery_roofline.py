"""Bytes-bound share of the ball-query kernel: its compulsory bytes a call
(planner_cost.ballquery_bytes_per_call: points and centres read once, k
indices a centre written) at the chip's HBM bandwidth, over its device
time a call."""
import planner_cost
import roofline


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    model = getattr(run, "planner_model", None)
    if (not t or not calls or model is None
            or not t["kernel_events"].get("ball_query")):
        return None
    per_call = t["kernel_s"]["ball_query"] / len(calls)
    need = planner_cost.ballquery_bytes_per_call(model,
                                                 run.planner_problems)
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bw / per_call
