"""setup_s: process start to the window's opening: imports and the
runtime, the corpus, its transfer, the index build or codec training, the
plan, and the warm-up of every bucket (compiles included on a cold cache)."""


def read(run):
    return run.setup_s
