"""App evaluations (output pixels) completed in the window, over the window:
from its start to the completion of its last frame."""


def read(rec):
    if rec["kind"] != "frames":
        return None
    return rec["frames"]["evals"] / rec["window_s"]
