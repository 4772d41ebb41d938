"""Model FLOP utilisation of the round, in %: the model FLOPs of one
round (from the configuration's shapes, ``model_flops_per_round`` in its
module; recomputation not counted) times the rounds of the traced window,
over the window and the chips' bf16 peak."""


def read(ctx):
    t = ctx.trace
    flops = getattr(ctx.model_mod, "model_flops_per_round", None)
    if t is None or flops is None or ctx.peak is None or t.window_s <= 0:
        return None
    done = flops(ctx.cfg, ctx.traffic) * t.rounds
    return 100.0 * done / (t.window_s * ctx.n_chips * ctx.peak["bf16_flops"])
