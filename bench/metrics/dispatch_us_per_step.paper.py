"""dispatch_us_per_step.paper: host self time of the resident jobs'
dispatch loops (span ``repro.dispatch``, less the history pulls nested in
it) per DPSVRG step of the traced window, as bench/scopes.py reduces it.
Moves paper_step_ms."""


def read(ctx):
    return ctx["scopes"].get("dispatch_us_per_step")
