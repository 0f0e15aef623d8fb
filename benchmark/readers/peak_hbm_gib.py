"""``memory_stats()["peak_bytes_in_use"]``, the fullest device, in GiB."""


def read(run, args):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
