"""plan_us_per_step.paper: host self time of the resident jobs' planning
(span ``repro.plan``) per DPSVRG step of the traced window, as
bench/scopes.py reduces it.  Moves paper_step_ms."""


def read(ctx):
    return ctx["scopes"].get("plan_us_per_step")
