"""Share of the traced window in which a collective runs on a device and
no other operation does (the collective time that compute does not
hide), averaged over the devices."""

import statistics


def read(record):
    tr = record["trace"]
    if tr is None or not tr["devices"]:
        return None
    return 100.0 * statistics.mean(tr["collective_exposed_s"]) / \
        tr["window_s"]
