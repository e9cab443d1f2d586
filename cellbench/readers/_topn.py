"""Window deltas of ``/debug/vars.topn`` (with-source TopN reads by
where their counts came from, and their candidate rows), shared by the
TopN metrics' readers. None where a surface lacks the block: an
untraced run, or a program without the counters."""


def delta(run) -> dict | None:
    if run.before is None or run.after is None:
        return None
    b = run.before["vars"].get("topn")
    a = run.after["vars"].get("topn")
    if a is None or b is None:
        return None
    return {k: a[k] - b.get(k, 0) for k in ("srcDevice", "srcHost",
                                             "candidates")}
