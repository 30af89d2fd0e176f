"""Share of the traced window in which a collective (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute) runs on a
device: the union of its collective intervals over the window, averaged
over the devices."""

import statistics


def read(record):
    tr = record["trace"]
    if tr is None or not tr["devices"]:
        return None
    return 100.0 * statistics.mean(tr["collective_s"]) / tr["window_s"]
