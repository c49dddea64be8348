"""Share of its roofline that the re-anchor reaches: the Cholesky of the
study's Gram and the inverse of its factor, counted at the re-anchored
study's active n whatever implements them, against the device time of the
Pallas Cholesky and triangular-solve kernels in the trace."""

KERNELS = r"^(cholesky_pallas|_trsv_pallas_raw)"


def flops(n: int) -> float:
    """Cholesky n^3/3 plus the inverse of a triangular factor n^3/3."""
    return 2.0 * n ** 3 / 3.0


def bytes_moved(n: int) -> float:
    """float32: read the Gram, write the factor; read it, write the
    inverse."""
    return 4.0 * 4 * n * n


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.trace.op_seconds(KERNELS)
    ns = ctx.work.get("reanchor_n", [])
    if secs <= 0 or not ns:
        return None
    f = sum(flops(n) for n in ns)
    b = sum(bytes_moved(n) for n in ns)
    least = max(f / ctx.peaks["flops_per_s"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
