"""Share of its roofline reached by the flash-attention backward.

The least time of the backward's work is counted once for each event of
its ``_dkv`` kernel, at that event's shape (``(batch, heads, seq,
head_dim)`` of dK on that device): 2.5 times the FLOPs of causal
attention's forward (five matmuls to its two), and the bytes of q, k, v
and dO read and of dq, dk and dv written, each element once, k, v, dk
and dv at the KV head count.  The time spent is the summed device time
of every event of the backward's kernels (``_stats``, ``_dkv`` and
``_dq``), so the share cannot pass 100% however many passes the
backward makes.  ``note`` says which of the two bounds sets most of the
least time."""

import re

from perfbench import counts

KERNEL = "toast_kernel__flash_attention_bwd__causal"
COUNTED = "_dkv"


def work_of(shape: str, config: dict) -> dict:
    b, h, s, hd = (int(x) for x in re.findall(r"\d+", shape.split("[")[1]))
    kv = h * config["num_key_value_heads"] / config["num_attention_heads"]
    flops = 2.5 * counts.attention_work(b, h, kv, s, hd)["flops"]
    return {"flops": flops, "bytes": 2.0 * b * s * hd * (3 * h + 4 * kv)}


def _least(record) -> dict:
    """Least seconds by bound, summed over the ``_dkv`` events."""
    total = {"flops": 0.0, "bytes": 0.0}
    for name, evs in record["trace"]["custom_calls"].items():
        if name.startswith(KERNEL) and COUNTED in name:
            for shape, _ in evs:
                t, which = counts.roofline_seconds(
                    work_of(shape, record["config"]), record["peak"])
                total[which] += t
    return total


def note(record) -> str:
    """Which bound, FLOPs or bytes, sets most of the least time."""
    if record["trace"] is None or record["peak"] is None:
        return "nothing to read"
    total = _least(record)
    if not any(total.values()):
        return "no backward kernel events"
    return f"bound by {max(total, key=total.get)} ({total})"


def read(record):
    tr = record["trace"]
    if tr is None or record["peak"] is None:
        return None
    spent = sum(d for name, evs in tr["custom_calls"].items()
                if name.startswith(KERNEL) for _, d in evs)
    if spent == 0.0:
        return None
    return 100.0 * sum(_least(record).values()) / spent
