"""Set-up time: process start, data from the seed, create, warm-up
(compilation or the compile cache), up to the window's start."""


def read(r):
    return r["setup_s"]
