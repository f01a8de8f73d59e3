"""grad_us_per_step.train: device time of the gradient evaluations (scopes
``repro.grad`` and ``repro.snapshot``, the snapshot refresh's gradient
included) per training step of the traced window, on the first chip, as
bench/scopes.py reduces it.  Moves train_tokens_per_s."""


def read(ctx):
    return ctx["scopes"].get("grad_us_per_step")
