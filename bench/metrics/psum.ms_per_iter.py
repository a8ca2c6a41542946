"""Device time of the collectives (the sharded fill's all-reduces of its
partials and their compensations), averaged over the devices, in
milliseconds per iteration executed."""


def read(ctx):
    iters = ctx["window"].get("iterations", 0)
    collective_s = ctx["trace"].get("collective_s", 0.0)
    if iters <= 0 or collective_s <= 0:
        return None
    return 1e3 * collective_s / iters
