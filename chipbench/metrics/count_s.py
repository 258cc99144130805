"""count_s: seconds per whole-graph count, over the counts that returned
inside the window (a count still running at the close is left out)."""
from readings import completed


def read(record):
    done = completed(record, "count")
    if not done:
        return None
    return sum(r["t_done"] - r["t_start"] for r in done) / len(done)
