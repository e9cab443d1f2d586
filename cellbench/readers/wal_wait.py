"""Mean WAL wait of the window's SetBit acks, as the server reports it
on each response (the key is absent where the wait was nought)."""


def read(run):
    acks = [r for r in run.records if r.ok and r.op.write]
    if not acks:
        return None
    return sum(r.stats.get("walWaitMs", 0.0) for r in acks) / len(acks)
