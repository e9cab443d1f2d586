"""Wall seconds of set-up's residency fills, summed over their
builders, from the /debug/vars read after the warm requests."""


def read(run):
    if run.before is None:
        return None
    seconds = (run.before["vars"].get("deviceBlockCache")
               or {}).get("fillSeconds")
    if seconds is None:
        return None
    return float(seconds)
