"""collective_us_per_step.paper: device time of collective operations
(the gossip's collective-permutes and the all-reduces of the node average)
per DPSVRG step of the traced window, on the first chip.  Moves
paper_step_ms."""


def read(ctx):
    seconds = ctx["trace"]["collective_s"]
    if not seconds or not ctx["steps"]:
        return None
    return 1e6 * seconds / ctx["steps"]
