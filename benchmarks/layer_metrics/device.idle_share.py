"""Device: percent of the traced window in which no operation ran on
the device: 1 - union of the operation intervals / window, averaged
over the chips used."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
