"""Set-up seconds: records made from the seed, the bulk load with its
filter builds, the device upload, and warming the cell's probe shape."""


def read(run):
    return run.setup_s
