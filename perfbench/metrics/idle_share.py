"""Share of the traced window in which no operation runs on a device:
1 - (union of the device's op intervals) / window, averaged over the
devices."""

import statistics


def read(record):
    tr = record["trace"]
    if tr is None or not tr["devices"]:
        return None
    return 100.0 * (1.0 - statistics.mean(tr["busy_s"]) / tr["window_s"])
