"""The whole step's share of the chip's peak: the operations the model
needs (`work/<config>.py`, recomputation not counted) for all the units
the window completed, over the window (host clock), over chips x peak."""


def read(ctx, work_fn):
    if not ctx["units"] or ctx["window_s"] <= 0 or ctx["peaks"] is None:
        return None
    fn = getattr(ctx["work"], work_fn)
    per_unit = (fn(ctx["cfg"], ctx["cell"], ctx["serve"]) if "serve" in ctx
                else fn(ctx["cfg"], ctx["cell"]))
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_unit * ctx["units"] / ctx["window_s"] / (
        ctx["chips"] * peak)
