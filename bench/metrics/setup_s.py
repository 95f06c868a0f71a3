"""Set-up time: process start to the first timed request or step, with
weight creation, compiles or cache loads and warm-up (host clock)."""


def read(record, trace, ctx):
    return record["setup_s"]
