"""stage pipeline: the compile registry's ``compile_wall_ns`` at the end of
set-up (wall of every call that compiled, first execution included)."""


def read(run):
    return run["setup"]["compile_wall_ns"] / 1e9
