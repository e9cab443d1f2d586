"""http_ms: wall time a read of the window spent in the stages
http_read, encode, http_write (see ``_stages``)."""

from . import _stages

STAGES = ('http_read', 'encode', 'http_write')


def read(run):
    return _stages.per_read_ms(run, STAGES)
