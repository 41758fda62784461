"""setup_s: process start to the end of warm-up (host clock), seconds:
imports, weights made on the device, engine built, every shape the cell's
traffic reaches compiled (or loaded from the compile cache) and run once."""


def read(run):
    return run.setup_s
