"""Share of the traced window in which no operation ran on the device, in
the frames cells."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "frames" or not t:
        return None
    return t["idle_share"] * 100.0
