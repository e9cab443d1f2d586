"""topn_replay_ms: wall time a read of the window spent in the stage
topn_replay (see ``_stages``); silent where the stage never ran."""

from . import _stages


def read(run):
    win = _stages.window(run)
    if win is None or not any("topn_replay" in win[where]
                              for where in ("stages", "offThread")):
        return None
    return _stages.wall_ms(win, ("topn_replay",))
