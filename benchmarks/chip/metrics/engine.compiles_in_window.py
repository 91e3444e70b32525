"""XLA backend compiles during the measured window (a program loaded from
the persistent compile cache is not one). Set-up warms every shape the
window uses, so this should read 0."""


def read(ctx):
    return ctx.compiles_in_window
