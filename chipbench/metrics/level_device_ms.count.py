"""level_device_ms.count: device ms of the vlftj level programs
(``core/vlftj.py`` ``_expand_level``, interior levels and the final level
alike) per whole-graph count that returned inside the traced window.

A count is the benchmark's ``execute`` span; an executable belongs to the
count whose span holds its midpoint.  The trace names these executables
after the jitted function: the names below are the ones a TPU v5e trace
shows.
"""
LEVEL_MODULES = ("jit__expand_level",)


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    counts = [(s, e) for name, s, e in tr["spans"]
              if name == "execute" and e <= tr["window_s"]]
    if not counts:
        return None
    level = [((s + e) / 2, e - s) for name, s, e in tr["modules"]
             if name.startswith(LEVEL_MODULES)]
    total = sum(d for mid, d in level
                if any(s <= mid <= e for s, e in counts))
    return 1000.0 * total / len(counts)
