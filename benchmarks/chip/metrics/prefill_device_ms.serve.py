"""Device time of the one-row prefill program (``jit_serve_prefill``) per
execution in the traced part of the window: its "XLA Modules" executions, by
name."""


def read(rec, ctx):
    if rec.trace is None or "module_n" not in rec.trace:
        return None
    s = sum(v for k, v in rec.trace["module_s"].items()
            if "serve_prefill" in k)
    n = sum(v for k, v in rec.trace["module_n"].items()
            if "serve_prefill" in k)
    return 1e3 * s / n if n else None
