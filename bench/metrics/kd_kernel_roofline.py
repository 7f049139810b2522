"""Least time of the fused KD kernel calls over their device time, in %.
The least time of a call is the larger of its bytes over the HBM
bandwidth and its operations over the peak (``bench/flops.kd_kernel_*``,
tiled operands and the 128-row block counted); the bytes bound it."""
from bench import flops, trace


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["peaks"] is None:
        return None
    kd, names = ctx["kd"], ctx["kd_names"]
    nbytes = flops.kd_kernel_bytes(kd["tokens"], kd["vocab"])
    nflops = flops.kd_kernel_flops(kd["tokens"], kd["vocab"])
    bw, peak = ctx["peaks"]["hbm_bytes_per_s"], ctx["peaks"]["bf16_flops_per_s"]
    least = busy = 0.0
    for kind in ("fwd", "bwd"):
        calls = ctx["kd_calls"][kind]
        busy += trace.time_of(t["op_s"], names[kind])
        least += calls * kd["lanes"] * max(nbytes[kind] / bw,
                                           nflops[kind] / peak)
    if busy <= 0:
        return None
    return 100.0 * least / busy
