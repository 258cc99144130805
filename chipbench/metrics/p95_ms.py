"""p95_ms: 95th-percentile ms from due to answer over the window's
interactive requests; refused and failed ones rank last."""
from readings import latency_ms


def read(record):
    return latency_ms(record, 95)
