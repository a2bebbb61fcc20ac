"""``cost.deviceMs`` of the replies: the host's clock around the launch
and the packed fetch.  Not kernel time."""


def read(run):
    ms = [(s["reply"].get("cost") or {}).get("deviceMs", 0.0) for s in run.samples if s["ok"]]
    return sum(ms) / len(ms) if ms else None
