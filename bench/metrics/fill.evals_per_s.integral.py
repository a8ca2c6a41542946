"""Evaluations the window asked of the fill kernel (``neval`` times the
iterations its integrals ran) over the device time of the Mosaic kernel's
operations, per device."""


def read(ctx):
    kernel_s = ctx["trace"]["kernel_s"]
    if kernel_s <= 0:
        return None
    return ctx["window"]["evals_requested"] / kernel_s
