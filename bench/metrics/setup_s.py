"""setup_s: from the process's start to the window's start: imports,
weights, compiling or loading the programs, the first phase."""


def read(ctx):
    return ctx.setup_s
