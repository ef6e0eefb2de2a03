"""The whole study's share of the chip's bf16 peak: the model's FLOPs of
the traced studies (forward and backward of every trained sample, forward
of every evaluated one, counted from shapes by ``counts`` and the model's
module) over the traced window's wall time."""
import counts


def read(ctx):
    t = ctx.trace
    if not t or not t["studies"]:
        return None
    flops = t["studies"] * counts.study_flops(ctx.cfg,
                                              ctx.traffic["eval_every"])
    return 100.0 * flops / t["window_s"] / ctx.peaks["bf16_flops_per_s"]
