"""Model FLOPs of the measured rounds (``bench/flops.round_flops``: the
students' training steps and the teachers' KD forwards on real rows, each
cluster teacher's refresh once, the test-set forward) over the window's
length and the chip's bf16 peak, in %."""


def read(ctx):
    if ctx["peaks"] is None or ctx["window_s"] <= 0:
        return None
    chips = ctx.get("chips", 1)
    return 100.0 * ctx["window_flops"] / (
        ctx["window_s"] * chips * ctx["peaks"]["bf16_flops_per_s"])
