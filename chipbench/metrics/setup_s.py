"""setup_s: process start to the first timed request (graph build,
server start, compile-cache loads, warm-up)."""


def read(record):
    return record["setup_s"]
