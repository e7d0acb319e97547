"""Training step's model FLOP utilization: model FLOPs per token (6 per
parameter plus attention, bench/harness/flops.py) times the window's
tokens per second, over the chips' peak for the cell's numbers (the int8
peak for AMR modes, bf16 for exact), in percent."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps"):
        return None
    peak = ctx.peaks[ctx.cell.workload["peak"]] * ctx.chips
    return 100.0 * c["flops_per_token"] * c["tokens"] / c["window_s"] / peak
