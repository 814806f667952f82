"""The whole step's share of the chip's bf16 peak: the FLOPs that
forward and backward require for the window's valid examples (from
shapes, `work/<config>.py`), over the window's time, over chips times
peak. Recomputation and the compressor do not count."""


def read(ctx):
    if not ctx["peaks"] or not ctx["window_s"]:
        return None
    flops = (ctx["work"].train_flops_per_example(ctx["config"], ctx["traffic"])
             * sum(ctx["valid_examples"]))
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / ctx["window_s"] / peak
