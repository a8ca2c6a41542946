"""Device time of the operations that are neither the fill kernel nor a
collective (the fill's wrapper, the adaptations, the estimate and the
combination), in milliseconds per iteration executed."""


def read(ctx):
    iters = ctx["window"].get("iterations", 0)
    if iters <= 0:
        return None
    return 1e3 * ctx["trace"]["xla_s"] / iters
