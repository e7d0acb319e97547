"""Decode step's model FLOP utilization: the model FLOPs of every token the
traced loop's decode steps produced (2 per parameter plus attention over
the token's context), over the host-clock time of those steps
(``ServeEngine.decode_seconds``), over the peak for the cell's numbers, in
percent."""


def read(ctx):
    c = ctx.counters
    if not c.get("decode_s"):
        return None
    peak = ctx.peaks[ctx.cell.workload["peak"]] * ctx.chips
    return 100.0 * c["decode_flops"] / c["decode_s"] / peak
