"""Route memo hit share across the window, from /debug/vars."""


def read(run):
    if run.before is None or run.after is None:
        return None
    b = run.before["vars"].get("routeMemo")
    a = run.after["vars"].get("routeMemo")
    if a is None or b is None:
        return None
    hits = a.get("hits", 0) - b.get("hits", 0)
    misses = a.get("misses", 0) - b.get("misses", 0)
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
