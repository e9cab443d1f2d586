"""Million bits a second through the HTTP import route during set-up."""


def read(run):
    load = run.load
    if not load or not load.get("bitsSeconds"):
        return None
    return load["bits"] / 1e6 / load["bitsSeconds"]
