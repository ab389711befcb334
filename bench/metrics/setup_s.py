"""Set-up: process start until the window opens (build, warm-up, server
and client start, the mix's warm-in). Host clock."""


def read(ctx):
    return ctx["setup_s"]
