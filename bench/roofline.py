"""A kernel's share of its roofline from the traced window."""


def share(ctx, kernel: str):
    """Least time over device time, in %; None where the trace holds no
    call of the kernel."""
    if ctx.trace is None or not ctx.peaks:
        return None
    t = ctx.trace["kernel_s"].get(kernel, 0.0)
    if t <= 0:
        return None
    return 100.0 * ctx.kernel_least_s(kernel) / t
