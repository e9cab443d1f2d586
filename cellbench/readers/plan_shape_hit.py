"""Share of the window's planned reads that were bound into a statement
shape already planned, from /debug/vars."""


def read(run):
    if run.before is None or run.after is None:
        return None
    b = run.before["vars"].get("planShapes")
    a = run.after["vars"].get("planShapes")
    if a is None or b is None:
        return None
    hits, misses, full = (a.get(k, 0) - b.get(k, 0)
                          for k in ("hits", "misses", "full"))
    if hits + misses + full <= 0:
        return None
    return 100.0 * hits / (hits + misses + full)
