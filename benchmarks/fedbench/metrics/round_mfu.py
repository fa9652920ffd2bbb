"""Model FLOPs of the window's rounds (``flops.rounds_flops``) over the
window's wall time and the chip's bf16 peak, in percent."""


def read(ctx):
    if not ctx["peak"]:
        return None
    rate = ctx["model_flops"] / ctx["window_s"]
    return 100.0 * rate / ctx["peak"]["peak_bf16_flops"]
