"""setup_s: seconds from process start until the window can open --
loading, weights, packing, engine pools, and warming (or compiling)
every shape the window uses.  Host clock."""


def read(ctx):
    return ctx.setup_s
