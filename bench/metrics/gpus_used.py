"""GPUs holding at least one workload after each event, averaged over all
events of the window in trace order."""


def read(rec):
    if rec["kind"] != "placement" or not rec["gpus_used"]:
        return None
    return sum(rec["gpus_used"]) / len(rec["gpus_used"])
