"""The Pallas segment-sum kernel's share of its HBM roofline.

Bytes per call come from the kernel's own HLO text in the trace: its
operands (the chunked f32 values, their int32 local ids and the
chunk-to-block table) read once and its result (the per-block f32 sums)
written once.  The one-hot matmul does about 2 * 128 operations per 8 bytes
read, far under the chip's FLOP-to-byte ratio, so HBM bandwidth is the
bound that applies.  Kernel time is the device time of its trace events."""

import kernel_cost


def read(ctx):
    if not ctx.trace:
        return None
    secs = moved = 0
    for name, s in ctx.trace["op_seconds"].items():
        if not kernel_cost.is_segment_sum(name):
            continue
        per_call = kernel_cost.hlo_bytes(ctx.trace["op_hlo"][name])
        if per_call is None:
            return None
        secs += s
        moved += ctx.trace["op_counts"][name] * per_call
    if not moved or secs <= 0:
        return None
    return 100.0 * moved / (secs * ctx.peaks["hbm_bytes_per_s"])
