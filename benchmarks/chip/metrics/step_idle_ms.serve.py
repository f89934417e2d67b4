"""Device idle time inside the engine's decode step, per step: the idle gaps
whose midpoint lies in a ``serve.step`` span or one of its children, over the
``serve.step`` spans in the traced part of the window."""

STEP = ("serve.step", "serve.decode", "serve.pin", "serve.fetch",
        "serve.sample", "serve.retire")


def read(rec, ctx):
    if rec.trace is None:
        return None
    n = rec.trace.get("program_span_n", {}).get("serve.step")
    if not n:
        return None
    idle = rec.trace["idle_s_by_program_span"]
    return 1e3 * sum(idle.get(k, 0.0) for k in STEP) / n
