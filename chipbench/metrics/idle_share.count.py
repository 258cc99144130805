"""idle_share.count: percent of the traced window in which no operation
ran on the device, in cells of whole-graph counts."""
from readings import idle_share


def read(record):
    return idle_share(record)
