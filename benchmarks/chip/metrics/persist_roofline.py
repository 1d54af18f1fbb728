"""Bytes-bound share of the persistent megakernel: the batch's compulsory
bytes (roofline.compulsory_bytes) at the chip's HBM bandwidth, over the
kernel's device time per call."""
import roofline


def read(run):
    t = run.trace
    calls = (t or {}).get("spans", {}).get("bench.execute", [])
    if not t or not calls or not t["kernel_events"].get("persist"):
        return None
    per_call = t["kernel_s"]["persist"] / len(calls)
    need = roofline.compulsory_bytes(run.level_widths, run.unit_obbs)
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bw / per_call
