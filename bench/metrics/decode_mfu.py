"""Model step, decode: FLOPs the active slots' tokens require (attention
over each one's own context) over the device time of the decode program in
the trace times the chip's bf16 peak.  Only the rounds inside the traced
part of the window count."""


def read(rec):
    tr, pk = rec.get("trace"), rec.get("peaks")
    if rec["kind"] != "served" or not tr or not pk:
        return None
    t0, t1 = rec["trace_interval"]
    flops = sum(r[4] for r in rec["rounds"] if r[0] >= t0 and r[1] <= t1)
    dev_s = sum(s for name, s in tr["programs"].items() if "_decode" in name)
    if dev_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (dev_s * pk["bf16_flops"])
