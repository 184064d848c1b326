"""Share of its roofline the fused plane kernel reaches: the least time
of every call's work (``bench/work.py``: 2·M·K·N at the int8 peak, or the
tier's packed weight prefix plus activations and outputs at the HBM
peak, whichever is longer), summed, over the kernel's device time in the
trace. Each call's M, K, N and plane count are read from its operand
shapes in the trace (``%fused_plane_linear.N = bf16[M,N] custom-call(
pair weights, s8[M,K], s32[P,K/32,N] magnitudes, ...)``); a kernel that
reads a layer's words in place from the stack, ``s32[L,P,K/32,N]``, with
an ``s32[1]`` layer index before the pair weights, is charged one layer's
work. The work of a linear is that of its product, whatever number of
plane passes computes it. Moves ``out_tok_s``."""

import re

KERNEL = re.compile(
    r"^%fused_plane_linear(?:\.\d+)? = \w+\[(\d+),(\d+)\]\S* custom-call\("
    r"(?:s32\[1\]\S* %\S+, )?\w+\[\d+\]\S* %\S+, s8\[(\d+),(\d+)\]\S* %\S+, "
    r"s32\[(?:\d+,)?(\d+),\d+,\d+\]"
)


def read(ctx):
    if ctx.trace is None:
        return None
    least = busy = 0.0
    for e in ctx.trace.ops(r"^%fused_plane_linear"):
        m = KERNEL.match(e.name)
        if m is None:
            raise RuntimeError(f"unparsed fused_plane_linear call: {e.name[:300]}")
        rows, n, _, k, planes = map(int, m.groups())
        least += ctx.work.least_seconds(rows, k, n, planes, ctx.peaks)
        busy += e.dur * 1e-9
    return 100.0 * least / busy if busy else None
