"""Share of the window's answered reads that a device program served."""


def read(run):
    reads = [r for r in run.records if r.ok and not r.op.write]
    if not reads:
        return None
    served = sum(1 for r in reads if r.stats.get("devicePrograms", 0) >= 1)
    return 100.0 * served / len(reads)
