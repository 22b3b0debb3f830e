"""Seconds the stock preparator's ``prepare_als_data`` took in set-up."""


def read(run):
    return run.get("setup", {}).get("als_pack_s")
