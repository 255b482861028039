"""A kernel's share of its roofline: the least time the chip could take
for the operations and bytes the algorithm needs (`work/<config>.py`,
per unit of work) over the kernel's time in the device trace."""

import flops
import trace_reduce


def read(ctx, pattern, work_fn):
    t = ctx["trace"]
    if t is None or not ctx["traced_units"] or ctx["peaks"] is None:
        return None
    seconds = trace_reduce.kernel_seconds(t, pattern)
    if not seconds:
        return None
    need = getattr(ctx["work"], work_fn)(ctx["cfg"], ctx["cell"])
    least = flops.roofline_seconds(need["flops"], need["bytes"],
                                   ctx["peaks"])
    return 100.0 * least["seconds"] * ctx["traced_units"] / seconds
