"""Device: peak bytes the fullest chip had to hold, in GB: the state
in use after the window plus the largest reservation a running program
made for its temporaries (``memory_stats()``: ``bytes_in_use`` +
``peak_bytes_reserved``), or ``peak_bytes_in_use`` where that is
larger. The same number as the result line's ``memory_peak_bytes``."""


def read(run):
    peak = run["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
