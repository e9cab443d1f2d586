"""XLA compilations counted between the last /status runtime sample
before the window and the first one after it."""


def read(run):
    if run.before is None or run.after is None:
        return None

    def first_calls(surfaces):
        runtime = surfaces["status"].get("runtime") or {}
        return (runtime.get("compileCache") or {}).get("firstCalls")

    b, a = first_calls(run.before), first_calls(run.after)
    if a is None or b is None:
        return None
    return a - b
