"""Reads of set-up that the router kept on the host path, from the
/debug/vars read after the warm requests."""


def read(run):
    if run.before is None:
        return None
    vetoes = run.before["vars"].get("costModelVetoes")
    if vetoes is None:
        return None
    return float(vetoes)
