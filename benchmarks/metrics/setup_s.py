"""Seconds from process start to the first timed step: imports, weights,
compiles, the loader's start and the checked and warm steps. Writing the store,
which a checkout's first run does, is left out: a training job reads its
dataset and does not write it."""


def read(run):
    return run['setup_s']
