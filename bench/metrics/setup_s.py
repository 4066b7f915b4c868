"""Set-up seconds: process start to the window's opening (weights, data,
compilation or cache loads, the first unit)."""


def read(rec):
    return rec["setup_s"]
