"""Meta-training's share of the chip's bf16 peak: the model FLOPs of one
meta-step (``flops.meta_step_flops``) times meta-steps per second over the
traced window, over the peak in ``bench/peaks.json``."""


def read(ctx):
    if not ctx.get("steps_per_s"):
        return None
    return 100.0 * ctx["step_flops"] * ctx["steps_per_s"] / \
        ctx["peaks"]["bf16_flops"]
