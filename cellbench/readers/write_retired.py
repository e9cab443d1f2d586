"""Resident slabs a write retired, across the window, from /debug/vars.

``deviceBlockCache.retired`` counts the slabs the residency cache
dropped and why: ``byWrite`` (a write changed a bit of their row),
``byWriteBytes`` (their bytes) and ``all`` (a whole view's retirement:
an import, a reopen). Divided by the window's acknowledged writes. None
where there is nothing to read: an untraced run, a program without the
counter, or no acknowledged write in the window."""


def _by_write(surface):
    retired = (surface["vars"].get("deviceBlockCache") or {}).get("retired")
    return None if retired is None else retired.get("byWrite")


def read(run):
    if run.before is None or run.after is None:
        return None
    acks = sum(1 for r in run.records if r.ok and r.op.write)
    b, a = _by_write(run.before), _by_write(run.after)
    if not acks or a is None or b is None:
        return None
    return (a - b) / acks
