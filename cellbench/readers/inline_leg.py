"""Share of the window's map-reduce fan-outs whose lone local leg ran
on its calling thread, from /debug/vars."""


def read(run):
    if run.before is None or run.after is None:
        return None
    b = run.before["vars"].get("legs")
    a = run.after["vars"].get("legs")
    if a is None or b is None:
        return None
    inline = a.get("inline", 0) - b.get("inline", 0)
    pooled = a.get("pooled", 0) - b.get("pooled", 0)
    if inline + pooled <= 0:
        return None
    return 100.0 * inline / (inline + pooled)
