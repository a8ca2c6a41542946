"""Seconds JAX spent inside the window tracing, lowering and compiling (or
fetching from the persistent cache), per integral completed: the cost of
``repro.core.run`` building its whole-run program again on every call."""


def read(ctx):
    w = ctx["window"]
    if w.get("integrals", 0) <= 0:
        return None
    spent = w["trace_s"] + w["lower_s"] + w["compile_s"]
    return spent / w["integrals"]
