"""mfu.train: the share of the chips' bf16 peak that the operations the
training rule requires (bench/flops.py, recomputation not counted) take at
the traced window's step rate.  Moves train_tokens_per_s."""


def read(ctx):
    flops = ctx["counts"].get("flops_per_step")
    if not flops or not ctx["steps"] or ctx["window_s"] <= 0:
        return None
    rate = flops * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"])
