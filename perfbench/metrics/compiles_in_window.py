"""Engine: programs compiled, or loaded from the compile cache, while the
window was open.  A warmed-up run reads 0."""


def read(run):
    return len(run.compiles)
