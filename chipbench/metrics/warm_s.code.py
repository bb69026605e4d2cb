"""Seconds to make the parameters from the seed, straight into every chip's
shards, and to warm every shape the cell's traffic uses (each prefill
bucket, the slot insert, the decode step), host clock."""


def read(run):
    return run.warm_s
