"""chunk_us_per_step.paper: device time of the resident chunk programs
(jitted ``exec_chunk``) per DPSVRG step of the traced window, on the first
chip.  Moves paper_step_ms."""


def read(ctx):
    seconds = ctx["trace"]["modules_s"].get("exec_chunk")
    if not seconds or not ctx["steps"]:
        return None
    return 1e6 * seconds / ctx["steps"]
