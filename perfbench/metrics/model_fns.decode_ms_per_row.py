"""Host time in ``decode_fn`` per live row it decodes."""
from harness.readers import ms_per


def read(run):
    return ms_per(run, ("decode",))
