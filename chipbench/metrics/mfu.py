"""The whole served path's share of the chip's bf16 peak: FLOPs the
completed requests needed, counted from the configuration's shapes, over
the window's wall seconds."""


def read(ctx):
    t = ctx["traffic"]
    flops = ctx["arch"].model_flops(ctx["config"], t["prompt_len"],
                                    t["gen_len"]) * ctx["completed"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"].flops_bf16
