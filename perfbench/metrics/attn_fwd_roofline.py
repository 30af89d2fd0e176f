"""Share of its roofline reached by the flash-attention forward kernel.

For every kernel event in the trace, the least time its work needs on
one chip (the larger of FLOPs over the peak and HBM bytes over the
peak bandwidth), summed, over the summed device time of the events.
``note`` says which of the two sets most of the least time.
The work is that of causal attention at the event's own shape
(``(batch, heads, seq, head_dim)`` on that device): half of the score
square, q and o at the query heads, k and v at the KV head count."""

import re

from perfbench import counts

KERNEL = "toast_kernel__flash_attention__causal"


def work_of(shape: str, config: dict) -> dict:
    b, h, s, hd = (int(x) for x in re.findall(r"\d+", shape.split("[")[1]))
    ratio = config["num_key_value_heads"] / config["num_attention_heads"]
    return counts.attention_work(b, h, h * ratio, s, hd)


def note(record) -> str:
    """Which bound, FLOPs or bytes, sets most of the least time."""
    tr = record["trace"]
    if tr is None or record["peak"] is None:
        return "nothing to read"
    total = {"flops": 0.0, "bytes": 0.0}
    for name, evs in tr["custom_calls"].items():
        if name.startswith(KERNEL):
            for shape, _ in evs:
                t, which = counts.roofline_seconds(
                    work_of(shape, record["config"]), record["peak"])
                total[which] += t
    if not any(total.values()):
        return "no kernel events"
    return f"bound by {max(total, key=total.get)} ({total})"


def read(record):
    tr = record["trace"]
    if tr is None or record["peak"] is None:
        return None
    least = spent = 0.0
    for name, evs in tr["custom_calls"].items():
        if not name.startswith(KERNEL):
            continue
        for shape, seconds in evs:
            least += counts.roofline_seconds(work_of(shape, record["config"]),
                                             record["peak"])[0]
            spent += seconds
    if spent == 0.0:
        return None
    return 100.0 * least / spent
