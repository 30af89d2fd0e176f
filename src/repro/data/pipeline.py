"""Deterministic synthetic token pipeline.

Batches are a pure function of ``(seed, step, host)`` — a restarted or
replaced host regenerates exactly its shard with no coordination, which is
the straggler/elasticity story for the data layer: no host ever blocks on
a data service, and recovery after preemption is recompute-free.

A background prefetch thread keeps ``prefetch`` batches ready so host-side
data generation overlaps device compute.  Its work shows in a profile as
the span ``toast.data.make``; the consumer's wait for a batch as
``toast.data.wait``, counted in ``Pipeline.waits_empty`` and
``Pipeline.wait_s``.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class DataConfig:
    seed: int = 0
    num_hosts: int = 1
    host_id: int = 0
    prefetch: int = 2


def _batch_for(cfg: ModelConfig, shape: ShapeConfig, dcfg: DataConfig,
               step: int) -> dict[str, np.ndarray]:
    """The global batch restricted to this host's rows."""
    B, S = shape.global_batch, shape.seq_len
    assert B % dcfg.num_hosts == 0, "global batch must divide hosts"
    local_b = B // dcfg.num_hosts
    rng = np.random.default_rng(
        np.random.SeedSequence([dcfg.seed, step, dcfg.host_id]))
    out = {}
    if cfg.is_encoder_decoder:
        S_tok = S // 2
        out["frames"] = rng.standard_normal(
            (local_b, S // 2, cfg.d_model), dtype=np.float32)
    elif cfg.frontend == "vision":
        S_tok = S - cfg.num_patches
        out["patch_embeds"] = rng.standard_normal(
            (local_b, cfg.num_patches, cfg.d_model), dtype=np.float32)
    else:
        S_tok = S
    # markov-ish synthetic tokens: next-token structure a model can learn
    tok = rng.integers(0, cfg.vocab_size, (local_b, S_tok), dtype=np.int32)
    tok[:, 1::2] = (tok[:, 0::2] * 31 + 7) % cfg.vocab_size
    out["tokens"] = tok
    if shape.kind == "train":
        out["targets"] = np.roll(tok, -1, axis=1)
    return out


class Pipeline:
    """Batches in step order from a prefetch thread.

    Attributes:
        waits_empty: calls of ``next`` that found no batch ready.
        wait_s: wall seconds ``next`` spent getting batches.
    """

    def __init__(self, cfg: ModelConfig, shape: ShapeConfig,
                 dcfg: DataConfig = DataConfig(), start_step: int = 0):
        self.cfg, self.shape, self.dcfg = cfg, shape, dcfg
        self._step = start_step
        self.waits_empty = 0
        self.wait_s = 0.0
        self._q: queue.Queue = queue.Queue(maxsize=max(dcfg.prefetch, 1))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        step = self._step
        while not self._stop.is_set():
            with span("data.make"):
                batch = _batch_for(self.cfg, self.shape, self.dcfg, step)
            while not self._stop.is_set():
                try:
                    self._q.put((step, batch), timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def __next__(self):
        t0 = time.perf_counter()
        with span("data.wait"):
            try:
                step, batch = self._q.get_nowait()
            except queue.Empty:
                self.waits_empty += 1
                step, batch = self._q.get()
        self.wait_s += time.perf_counter() - t0
        return step, batch

    def __iter__(self):
        return self

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2)


def batch_at(cfg: ModelConfig, shape: ShapeConfig, dcfg: DataConfig,
             step: int) -> dict[str, np.ndarray]:
    """Random access for tests and recovery checks."""
    return _batch_for(cfg, shape, dcfg, step)
