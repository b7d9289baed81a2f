"""Set-up: process start to the window's start (imports, the device, weights,
compilation or cache loads, warm-up, the mix's starting state)."""


def read(r):
    return r.setup_s
