"""Named host spans on the profiler's clock.

:func:`span` marks one phase of host work.  It always opens a
``jax.profiler.TraceAnnotation`` named ``toast.<name>``, so that a
profile taken around the program shows the phase on its host plane, on
the same clock as the device's operations; with no profiler running
that costs about a microsecond.  Given a dict, it also adds the
phase's wall seconds to ``into[name]``, which is how the analysis and
the partitioner keep their phase timers.

Spans mark phases, never the inside of a search loop: counters there
stay in ``EvalStats``.  Device work is named with ``jax.named_scope``
where it is traced (``attn_bwd``, ``mlp``, ``head_loss``,
``optimizer``); see ``docs/api.md``.
"""

from __future__ import annotations

import contextlib
import time

import jax

PREFIX = "toast."


@contextlib.contextmanager
def span(name: str, into: dict | None = None):
    """Mark the host work inside the block as the span ``toast.<name>``.

    Args:
        name: the span's name without the ``toast.`` prefix.
        into: if given, the block's wall seconds are added to
            ``into[name]`` when it ends, also when it raises.
    """
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
    finally:
        if into is not None:
            into[name] = into.get(name, 0.0) + time.perf_counter() - t0
