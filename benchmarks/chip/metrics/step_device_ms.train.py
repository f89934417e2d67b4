"""Device time of the train-step program per step in the traced part of the
window: its "XLA Modules" executions, by name."""


def read(rec, ctx):
    if rec.trace is None or not rec.counters.get("traced_steps"):
        return None
    s = sum(v for k, v in rec.trace["module_s"].items()
            if "train_step" in k)
    return 1e3 * s / rec.counters["traced_steps"] if s else None
