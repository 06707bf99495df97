"""Process start to the first stamp of the window: imports, the program's
set-up, compilation or the compile cache's load, the checked first steps."""


def reduce(ctx):
    return ctx["stamps"][ctx["window"][0]][0] - ctx["process_start"]
