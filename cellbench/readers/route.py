"""route_ms: wall time a read of the window spent in the stages
execute, leg, route, merge (see ``_stages``)."""

from . import _stages

STAGES = ('execute', 'leg', 'route', 'merge')


def read(run):
    return _stages.per_read_ms(run, STAGES)
