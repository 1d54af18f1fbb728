"""Bytes-bound share of the FPS kernel: its compulsory bytes a call
(planner_cost.fps_bytes_per_call: the clouds read once, the picks written)
at the chip's HBM bandwidth, over its device time a call.  FPS is a chain
of dependent picks, so this share is small by nature: it says how far the
pick loop's latency stands from streaming the points once."""
import planner_cost
import roofline


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    model = getattr(run, "planner_model", None)
    if (not t or not calls or model is None
            or not t["kernel_events"].get("fps_sample")):
        return None
    per_call = t["kernel_s"]["fps_sample"] / len(calls)
    need = planner_cost.fps_bytes_per_call(model, run.planner_problems)
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bw / per_call
