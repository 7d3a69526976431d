"""Seconds of tracing, lowering and compiling (or loading) in set-up."""


def read(r):
    return r.spans["compile_s"]
