"""device_idle.paper: the share of the traced window of paper jobs in which
no operation ran on the device, averaged over the chips used.  Moves
paper_step_ms."""


def read(ctx):
    trace = ctx["trace"]
    if trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
