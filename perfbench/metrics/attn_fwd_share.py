"""Share of the traced window taken by the flash-attention forward
kernel: the summed device time of its events over the window, averaged
over the devices."""

KERNEL = "toast_kernel__flash_attention__causal"


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    events = [d for name, evs in tr["custom_calls"].items()
              if name.startswith(KERNEL) for _, d in evs]
    if not events:
        return None
    return 100.0 * sum(events) / (tr["window_s"] * tr["devices"])
