"""Device memory peak of the fullest chip after the window: the runtime's
``peak_bytes_in_use`` plus ``peak_bytes_reserved``, the region it holds for
the compiled programs' temporaries (GiB)."""


def read(run):
    return run["memory_peak_bytes"] / 2 ** 30
