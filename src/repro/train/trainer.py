"""Training loop driver.

Runs the protocol-neutral train step over the synthetic sharded pipeline,
cycling the gossip phase through the schedule (static-phase compiled variants
are cached by phase index). Works on a real mesh or the single-device smoke
mesh alike.

Dispatch pipelining: jax dispatches steps asynchronously, so the host can run
ahead of the device — essential for ``gossip_async``, whose step-t wire
transfer settles while the next ``staleness`` steps' compute executes (the
bounded-delay ring consumes it k steps after dispatch). Unbounded run-ahead,
however, queues arbitrarily many host batches and step outputs, so the
trainer keeps a **bounded in-flight window**: at most ``2 + 2 * staleness``
dispatched-but-unfinished steps (the deeper the ring, the more steps must be
allowed in flight for the overlap to materialize; tunable via
``inflight_window``); beyond that it blocks on the oldest step's metrics
before dispatching more.

Buffer donation: packed states (bundle.layout set) donate the state into the
step, so the per-bucket gossip mix writes onto the previous step's buffers
instead of double-allocating; the caller's state object is consumed
(``Trainer.state`` always holds the live one). Per-leaf states keep
``donate=False`` — their scan-stacked leaves alias model views that XLA
cannot always reuse.

Host spans: each step runs inside a ``repro.step`` span (``step_num`` and
``phase`` as arguments) holding ``repro.dispatch`` (the jitted call),
``repro.wait`` (blocked on the oldest in-flight step) and ``repro.input``
(the next batch); ``repro.drain`` is the metrics' trip to the host. They are
``jax.profiler`` annotations, written only while a profiler trace runs.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.data import ShardedTokenDataset, make_replica_batches
from .step import TrainStepBundle

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, bundle: TrainStepBundle, state: Any,
                 dataset: ShardedTokenDataset,
                 log_every: int = 10,
                 log_fn: Callable[[str], None] = print,
                 inflight_window: Optional[int] = None,
                 donate: Optional[bool] = None):
        self.bundle = bundle
        self.state = state
        self.dataset = dataset
        self.log_every = log_every
        self.log_fn = log_fn
        self.staleness = getattr(bundle.protocol, "staleness", 0)
        # async protocols get a deeper window: step t's transfer must be able
        # to stay in flight while t+1 dispatches.
        self.inflight_window = (inflight_window if inflight_window is not None
                                else 2 + 2 * self.staleness)
        # packed states donate: buckets mix in place instead of reallocating
        self.donate = (bundle.layout is not None) if donate is None else donate
        self._steps_cache: Dict[Any, Callable] = {}
        self._inflight: collections.deque = collections.deque()
        self.history: List[Dict[str, float]] = []

    def step_fn(self, phase: int):
        """The jitted step for schedule ``phase`` (folded by the period)."""
        period = max(self.bundle.protocol.period, 1)
        phase = phase % period
        if phase not in self._steps_cache:
            self._steps_cache[phase] = self.bundle.jitted(phase,
                                                          donate=self.donate)
        return self._steps_cache[phase]

    def _drain(self, pending: List) -> None:
        """Materialize queued device metrics into float history records.
        The only host sync in the loop — called on log boundaries and at the
        end of ``run``, never per step (a per-step ``float(v)`` blocks
        dispatch and serializes compute with the host). One ``device_get``
        brings every pending scalar over at once."""
        with jax.profiler.TraceAnnotation("repro.drain"):
            host = jax.device_get([metrics for _, metrics in pending])
        for (step, _), metrics in zip(pending, host):
            rec = {k: float(v) for k, v in metrics.items()}
            rec["step"] = step
            self.history.append(rec)
        pending.clear()
        self._inflight.clear()

    def _bound_inflight(self, metrics) -> None:
        """Cap host run-ahead: block on the oldest dispatched step once more
        than ``inflight_window`` steps are in flight."""
        token = jax.tree.leaves(metrics)[0]
        self._inflight.append(token)
        while len(self._inflight) > self.inflight_window:
            oldest = self._inflight.popleft()
            if hasattr(oldest, "block_until_ready"):
                with jax.profiler.TraceAnnotation("repro.wait"):
                    oldest.block_until_ready()

    def batch(self, step: int):
        """Step ``step``'s (dp, local_b, ...) batch, each replica's slice
        placed straight on its own devices."""
        with jax.profiler.TraceAnnotation("repro.input"):
            host = make_replica_batches(self.dataset, step,
                                        max(self.bundle.dist.dp, 1))
            return jax.device_put(host, self.bundle.batch_shardings)

    def run(self, num_steps: int, start_step: int = 0) -> List[Dict[str, float]]:
        batch = self.batch(start_step)
        t0 = time.perf_counter()
        pending: List = []  # (step, device-side metrics) not yet transferred
        period = max(self.bundle.protocol.period, 1)
        for step in range(start_step, start_step + num_steps):
            with jax.profiler.StepTraceAnnotation(
                    "repro.step", step_num=step, phase=step % period):
                fn = self.step_fn(step)
                with jax.profiler.TraceAnnotation("repro.dispatch"):
                    self.state, rotated, metrics = fn(self.state, batch)
                pending.append((step, metrics))
                self._bound_inflight(metrics)
                if self.log_every and step % self.log_every == 0:
                    self._drain(pending)
                    rec = self.history[-1]
                    dt = time.perf_counter() - t0
                    self.log_fn(f"step {step:5d} loss "
                                f"{rec.get('loss', 0):.4f} ce "
                                f"{rec.get('ce', 0):.4f} ({dt:.1f}s)")
                # fresh data each step; the device-side rotation is
                # exercised in the step itself, the pipeline applies the
                # equivalent host-side shard rotation for the *next* step's
                # content.
                batch = self.batch(step + 1)
        self._drain(pending)
        return self.history
