"""Device time of the fused KD kernels (forward and backward) over the
device's busy time, in %, from the profiler trace."""
from bench import trace


def read(ctx):
    t = ctx["trace"]
    if t is None or t["busy_s"] <= 0:
        return None
    kd = trace.time_of(t["op_s"], ctx["kd_names"]["fwd"]
                       + ctx["kd_names"]["bwd"])
    if kd <= 0:
        return None
    return 100.0 * kd / (t["busy_s"] * t["devices"])
