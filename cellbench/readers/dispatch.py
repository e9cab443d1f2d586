"""dispatch_ms: wall time a read of the window spent in the stages
dispatch, compile (see ``_stages``)."""

from . import _stages

STAGES = ('dispatch', 'compile')


def read(run):
    return _stages.per_read_ms(run, STAGES)
