"""Mean host time of the engine's per-slot sampling and bookkeeping loop: the
``serve.sample`` spans the program kept in memory through the window."""


def read(rec, ctx):
    try:
        from repro import obs
    except ImportError:          # a program without its own spans
        return None
    d = [s.end_ns - s.start_ns for s in obs.spans()
         if s.name == "serve.sample"]
    return 1e-6 * sum(d) / len(d) if d else None
