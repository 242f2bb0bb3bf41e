"""Device busy time (union of device-operation intervals in the profiler
trace over the window) per frame completed in the window."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "frames" or not t or not rec["frames"]["completed"]:
        return None
    return t["busy_s"] * 1e3 / rec["frames"]["completed"]
