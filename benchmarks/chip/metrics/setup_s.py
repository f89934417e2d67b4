"""Set-up time: process start to the window's opening (platform, weights,
compiles or compile-cache loads, warm-up)."""


def read(rec, ctx):
    return rec.setup_s
