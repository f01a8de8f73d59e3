"""collective_exposed_us_per_step.paper: the part of the collective
operations' device time (as collective_us_per_step.paper counts it) during
which no other operation ran on the first chip, per DPSVRG step of the
traced window: exchange time that nothing hides.  Moves paper_step_ms."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["collective_s"] or not ctx["steps"]:
        return None
    return 1e6 * trace["collective_exposed_s"] / ctx["steps"]
