"""parse_plan_ms: wall time a read of the window spent in the stages
parse, plan (see ``_stages``)."""

from . import _stages

STAGES = ('parse', 'plan')


def read(run):
    return _stages.per_read_ms(run, STAGES)
