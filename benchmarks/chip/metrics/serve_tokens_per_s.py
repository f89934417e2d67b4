"""Output tokens of the requests due in the window, over the time from the
window's start until the last of them finished."""


def read(rec, ctx):
    done = [r for r in rec.requests if r["done"]]
    if not done:
        return None
    tokens = sum(len(r["times"]) for r in done)
    return tokens / max(r["times"][-1] for r in done)
