"""The whole FL round's share of the chips' bf16 peak, in %: the forward +
backward FLOPs that the selected clients' local training required in the
traced window (from the model's shapes, counted by the configuration's
reference module: ``trial_train_flops``) ÷ the window's seconds ÷ (chips ×
peak).  Padding and eval are not counted; under fedsgd each valid sample
is one forward + backward."""


def read(ctx):
    flops = ctx["work"].get("train_flops")
    if not flops:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / ctx["window_s"] / peak
