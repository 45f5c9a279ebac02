"""Peak use of the fullest device's memory by the runtime's counters
(``run.py`` ``peak_bytes``: ``memory_stats()`` ``peak_bytes_in_use`` +
``peak_bytes_reserved``), read after the windows and before the checks."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 2.0 ** 30
