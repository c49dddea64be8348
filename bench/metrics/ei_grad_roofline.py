"""Share of its roofline that the fused EI value+gradient kernel reaches.

The least time the window's EI work needs is the larger of its operations
over the peak rate and its bytes over the HBM rate, each counted at every
computed slot's active n and the configured R restarts (not at the padded
buffer or the padded restart tile), for the ascent steps plus the final
evaluation.  It is divided by the device time of the kernel's ops in the
trace (`fused_ei_grad_pallas`, the in-loop and the final call).
"""

KERNEL = r"fused_ei_grad_pallas"


def flops(n: int, r: int, d: int) -> float:
    """One call for one slot: cross-gram (distance + Matern, ~3d + 12 per
    pair), the mean (2 per pair), U = K A (2 n per pair), the variance (2
    per pair), EI (~30 per row), and the gradient (~12 + 2d per pair)."""
    return 2.0 * r * n * n + r * n * (5.0 * d + 28.0) + 30.0 * r


def bytes_moved(n: int, r: int, d: int) -> float:
    """One call for one slot, float32: A (n x n), the train points, alpha
    and the mask read once; the candidates read and the EI and gradient
    written."""
    return 4.0 * (n * n + n * d + 2 * n + 2 * r * d + r)


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.op_seconds(KERNEL)
    calls = ctx.work.get("ei_calls", [])
    if secs <= 0 or not calls:
        return None
    f = sum(c * flops(n, r, d) for c, n, r, d in calls)
    b = sum(c * bytes_moved(n, r, d) for c, n, r, d in calls)
    least = max(f / ctx.peaks["flops_per_s"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
