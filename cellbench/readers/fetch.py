"""fetch_ms: wall time a read of the window spent in the stage
fetch (see ``_stages``)."""

from . import _stages

STAGES = ('fetch',)


def read(run):
    return _stages.per_read_ms(run, STAGES)
