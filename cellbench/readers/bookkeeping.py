"""bookkeeping_ms: wall time a read of the window spent in the stages
setup, finish (see ``_stages``)."""

from . import _stages

STAGES = ('setup', 'finish')


def read(run):
    return _stages.per_read_ms(run, STAGES)
