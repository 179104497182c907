"""Backend compile requests inside the measured window (persistent-cache
loads included); anything but 0 means the warm-up missed a shape."""


def read(ctx):
    return ctx["window_compiles"]
