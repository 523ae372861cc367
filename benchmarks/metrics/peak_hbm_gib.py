"""Peak device memory of the fullest chip, read right after the window: the
runtime's peak of live arrays plus the compiled step's temporary buffers."""


def read(run):
    return run['peak_bytes'] / 2 ** 30 if run['peak_bytes'] else None
