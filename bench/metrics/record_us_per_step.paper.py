"""record_us_per_step.paper: device time of the on-device record kernel
(jitted ``record``) per DPSVRG step of the traced window, on the first
chip.  Moves paper_step_ms."""


def read(ctx):
    seconds = ctx["trace"]["modules_s"].get("record")
    if not seconds or not ctx["steps"]:
        return None
    return 1e6 * seconds / ctx["steps"]
