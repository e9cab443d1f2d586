"""admission_wait_ms: wall time a read of the window spent in the stage
admission (see ``_stages``)."""

from . import _stages

STAGES = ('admission',)


def read(run):
    return _stages.per_read_ms(run, STAGES)
