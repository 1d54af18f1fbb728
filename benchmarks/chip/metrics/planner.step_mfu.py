"""Share of the chip's bf16 peak that the MPiNets network's FLOPs take of
one planning call: the network's FLOPs a call, counted from the published
shapes (planner_cost.flops_per_call), over the mean ``bench.execute`` span
times the peak.  The span holds the whole call (rollout, host work and
the collision gate), and the denominator is the bfloat16 peak while the
configuration's matmuls are float32-accurate (several bf16 passes each),
so this reads 3-6x below what the same step would read in bfloat16."""
import planner_cost
import roofline


def read(run):
    calls = ((run.trace or {}).get("spans") or {}).get("bench.execute")
    model = getattr(run, "planner_model", None)
    if not calls or model is None:
        return None
    span = sum(e - s for s, e, _ in calls) / len(calls)
    flops = planner_cost.flops_per_call(model, run.planner_problems)
    peak = roofline.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (span * peak)
