"""The whole FL round's share of the chips' bf16 peak, in %: the
forward + backward FLOPs one valid trained sample requires (from the CNN's
shapes, ``bench.work``) × the valid samples the selected clients trained in
the traced window ÷ the window's seconds ÷ (chips × peak).  Padding and
eval are not counted; under fedsgd each sample is one forward + backward."""
from bench import work


def read(ctx):
    w = ctx["work"]
    if not w.get("trained_samples"):
        return None
    flops = work.cnn_train_flops_per_sample(ctx["config"]) * w["trained_samples"]
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / ctx["window_s"] / peak
