"""Set-up seconds: process start to the first op of the window, with
loading, preload, warm-up and any compiling."""


def read(r):
    return r.setup_s
