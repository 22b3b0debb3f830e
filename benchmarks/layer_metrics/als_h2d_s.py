"""Seconds to place the packed blocks and factors on the device, to ready."""


def read(run):
    return run.get("setup", {}).get("als_h2d_s")
