"""Training listeners.

TPU-native equivalent of DL4J's listener pipeline (reference:
``deeplearning4j-nn .../optimize/listeners/{ScoreIterationListener,
PerformanceListener,EvaluativeListener,CheckpointListener}.java``† per
SURVEY.md §2.4/§5; reference mount was empty, citations upstream-relative,
unverified).

Hook contract: ``iteration_done(model, iteration, epoch)`` after every
optimizer step; ``on_epoch_end(model)`` after each epoch. Matches DL4J's
TrainingListener events that matter; forward/backward sub-events don't exist
here (the step is one fused XLA program — by design).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Callable, List, Optional

log = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    def iteration_done(self, model, iteration: int, epoch: int):
        pass

    def on_epoch_end(self, model):
        pass


class ScoreIterationListener(TrainingListener):
    """Log the score every N iterations (DL4J ScoreIterationListener)."""

    def __init__(self, print_iterations: int = 10, printer: Callable = None):
        self.n = max(1, print_iterations)
        self._print = printer or (lambda s: log.info(s))

    def iteration_done(self, model, iteration, epoch):
        if iteration % self.n == 0:
            self._print(f"Score at iteration {iteration} is {model.score()}")


class CollectScoresListener(TrainingListener):
    """Record (iteration, score) pairs (DL4J CollectScoresIterationListener)."""

    def __init__(self):
        self.scores: List[tuple] = []

    def iteration_done(self, model, iteration, epoch):
        self.scores.append((iteration, model.score()))


class PerformanceListener(TrainingListener):
    """Throughput tracking: examples/sec, iterations/sec (DL4J
    PerformanceListener), plus optional MFU given a per-example FLOP count —
    the TPU-era metric the reference lacked (SURVEY.md §5 tracing row) —
    and per-interval device HBM telemetry (PJRT ``memory_stats()``:
    peak_bytes_in_use/bytes_limit; ``last_memory`` stays None on backends
    like CPU that don't report them)."""

    def __init__(self, frequency: int = 10, batch_size: Optional[int] = None,
                 flops_per_example: Optional[float] = None,
                 peak_flops: Optional[float] = None, printer: Callable = None,
                 collect_memory: bool = True, collect_resilience: bool = True,
                 collect_phases: bool = True):
        self.frequency = max(1, frequency)
        self.batch_size = batch_size
        self.flops_per_example = flops_per_example
        # the peak is only needed where an MFU is computed from it
        self.peak_flops = peak_flops or (
            _detect_peak_flops() if flops_per_example else None)
        self.collect_memory = collect_memory
        self.collect_resilience = collect_resilience
        self.collect_phases = collect_phases
        self._print = printer or (lambda s: log.info(s))
        self._t0 = None
        self._it0 = 0
        self.last_examples_per_sec = float("nan")
        self.last_mfu = float("nan")
        self.last_memory: Optional[dict] = None
        self.last_resilience: Optional[dict] = None
        self.last_phases: Optional[dict] = None

    def iteration_done(self, model, iteration, epoch):
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
            self._it0 = iteration
            return
        if (iteration - self._it0) % self.frequency:
            return
        dt = now - self._t0
        iters = iteration - self._it0
        if dt <= 0 or iters <= 0:
            return
        its_per_sec = iters / dt
        msg = f"iteration {iteration}: {its_per_sec:.2f} it/s"
        if self.batch_size:
            eps = its_per_sec * self.batch_size
            self.last_examples_per_sec = eps
            msg += f", {eps:.1f} examples/s"
            if self.flops_per_example and self.peak_flops:
                # 3x fwd flops approximates fwd+bwd
                self.last_mfu = 3 * self.flops_per_example * eps / self.peak_flops
                msg += f", MFU {self.last_mfu * 100:.1f}%"
        if self.collect_memory:
            from ..nn.memory import device_memory_stats
            self.last_memory = device_memory_stats()
            if self.last_memory:
                msg += (f", hbm peak "
                        f"{self.last_memory['peak_bytes_in_use'] / 2**30:.2f}"
                        f"/{self.last_memory['bytes_limit'] / 2**30:.2f} GiB")
        if self.collect_phases:
            # step-phase split over THIS interval (ISSUE 6): the fit loops
            # record data-wait and step-dispatch durations into the
            # registry; windowing by the interval keeps the numbers
            # current instead of lifetime
            from ..runtime import telemetry as _tel
            lbl = getattr(model, "telemetry_label", None)
            mlabels = {} if lbl is None else {"model": lbl}
            wait = _tel.histogram("train.phase.data_wait_s") \
                .hist_snapshot(window=dt, **mlabels)
            disp = _tel.histogram("train.phase.step_s") \
                .hist_snapshot(window=dt, **mlabels)
            self.last_phases = {
                "data_wait_ms_p50": None if wait["p50"] is None
                else wait["p50"] * 1e3,
                "step_dispatch_ms_p50": None if disp["p50"] is None
                else disp["p50"] * 1e3,
                "data_wait_count": wait["count"],
            }
            if wait["p50"] is not None and disp["p50"] is not None:
                msg += (f", wait/dispatch p50 {wait['p50'] * 1e3:.1f}/"
                        f"{disp['p50'] * 1e3:.1f}ms")
        if self.collect_resilience and hasattr(model, "resilience_counters"):
            # divergence-sentinel counters (the interval's ONE deliberate
            # device sync — frequency-gated) + checkpoint/restore telemetry
            from ..runtime import faults as _faults
            rc = dict(model.resilience_counters())
            rc.update(_faults.telemetry_snapshot())
            self.last_resilience = rc
            if rc["bad_total"]:
                msg += f", skipped {rc['bad_total']} non-finite steps"
            if rc["clip_events"]:
                msg += f", {rc['clip_events']} clip events"
            if rc.get("checkpoint_last_save_latency_s") is not None:
                msg += (f", ckpt save "
                        f"{rc['checkpoint_last_save_latency_s'] * 1e3:.0f}ms")
            if rc.get("restore_count"):
                msg += f", {rc['restore_count']} restores"
        self._print(msg)
        self._t0 = now
        self._it0 = iteration


def _detect_peak_flops() -> float:
    """Peak bf16 FLOP/s of device 0, for MFU: ``DL4J_TPU_PEAK_FLOPS`` when
    set, else the published table by exact ``device_kind``
    (``runtime.attribution.DEVICE_PEAKS``). An unknown device raises."""
    from ..runtime.attribution import _env_peak, published_peaks
    env = _env_peak("DL4J_TPU_PEAK_FLOPS")
    return env if env is not None else published_peaks()["flops_per_s"]


class EvaluativeListener(TrainingListener):
    """Periodic evaluation against a held-out iterator (DL4J EvaluativeListener)."""

    def __init__(self, iterator, frequency_epochs: int = 1, printer: Callable = None):
        self.iterator = iterator
        self.frequency = max(1, frequency_epochs)
        self._print = printer or (lambda s: log.info(s))
        self.last_evaluation = None

    def on_epoch_end(self, model):
        if model.epoch % self.frequency:
            return
        ev = model.evaluate(self.iterator)
        self.last_evaluation = ev
        self._print(f"epoch {model.epoch}: accuracy={ev.accuracy():.4f} f1={ev.f1():.4f}")


class CheckpointListener(TrainingListener):
    """Periodic rotating checkpoints (DL4J CheckpointListener semantics:
    save every N epochs/iterations, keep last K)."""

    def __init__(self, directory: str, save_every_epochs: Optional[int] = 1,
                 save_every_iterations: Optional[int] = None, keep_last: int = 3):
        self.dir = directory
        self.every_epochs = save_every_epochs
        self.every_iters = save_every_iterations
        self.keep_last = keep_last
        self._saved: List[str] = []
        os.makedirs(directory, exist_ok=True)

    def _save(self, model, tag: str):
        path = os.path.join(self.dir, f"checkpoint_{tag}.zip")
        model.save(path)
        self._saved.append(path)
        while len(self._saved) > self.keep_last:
            old = self._saved.pop(0)
            try:
                os.remove(old)
            except OSError:
                pass

    def iteration_done(self, model, iteration, epoch):
        if self.every_iters and iteration and iteration % self.every_iters == 0:
            self._save(model, f"iter_{iteration}")

    def on_epoch_end(self, model):
        if self.every_epochs and model.epoch % self.every_epochs == 0:
            self._save(model, f"epoch_{model.epoch}")
