"""device_idle.train: the share of the traced training window in which no
operation ran on the device, averaged over the chips used.  Moves
train_tokens_per_s."""


def read(ctx):
    trace = ctx["trace"]
    if trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
