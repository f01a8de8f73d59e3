"""opt_us_per_step.train: device time of the optimizer's tree work (scopes
``repro.update``, ``repro.mix`` and ``repro.prox``; XLA fuses the prox into
the mix) per training step of the traced window, on the first chip, as
bench/scopes.py reduces it.  Moves train_tokens_per_s."""


def read(ctx):
    return ctx["scopes"].get("opt_us_per_step")
