"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window: the whole process, set-up included."""


def read(run):
    peak = run["memory_peak_bytes"]
    return None if peak is None else peak / 2**30
