"""Set-up time: process start to the first due request (host clock)."""


def read(rec):
    return rec["setup_s"]
