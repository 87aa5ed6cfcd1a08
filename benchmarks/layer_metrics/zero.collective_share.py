"""ZeRO exchange: percent of the traced window in which a collective
runs on a device and no compute does (the exposed part of the gradient
exchange and the parameter gather), from the device trace, averaged
over the chips used."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
