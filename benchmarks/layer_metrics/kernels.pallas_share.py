"""Train kernels: percent of the device's busy time spent inside the
Pallas kernels (flash attention forward and backward, fused norms),
from the device trace: self time of the custom-call events over busy
time, averaged over the chips used."""


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["pallas_s"] / t["busy_s"]
