"""Set-up: process start to the window's opening (load, build, warm-up,
compiles), on the host clock."""


def read(record):
    return record["setup_s"]
