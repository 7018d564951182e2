"""Training listeners.

TPU-native equivalents of the reference's ``optimize/api/IterationListener`` /
``TrainingListener`` SPI and the impls in ``optimize/listeners/``:
``ScoreIterationListener``, ``PerformanceListener`` (samples/sec + batches/sec
at ``PerformanceListener.java:99-102``), ``CollectScoresIterationListener``,
``ParamAndGradientIterationListener``.

Listeners run on the host after each jitted step; the score is the only value
fetched from device per iteration, so the hot path stays one XLA program
(SURVEY.md §7 hard part f — listeners must stay off the hot path).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Tuple

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")


class IterationListener:
    """Reference ``IterationListener`` contract."""

    def iteration_done(self, model, iteration: int) -> None:
        raise NotImplementedError


class TrainingListener(IterationListener):
    """Adds epoch/forward/backward hooks (reference ``TrainingListener``)."""

    def on_epoch_start(self, model) -> None:
        pass

    def on_epoch_end(self, model) -> None:
        pass

    def iteration_done(self, model, iteration: int) -> None:
        pass


class ScoreIterationListener(IterationListener):
    """Log score every N iterations (reference
    ``ScoreIterationListener.java``)."""

    def __init__(self, print_iterations: int = 10, out=None):
        self.print_iterations = max(1, print_iterations)
        self._out = out

    def iteration_done(self, model, iteration: int) -> None:
        if iteration % self.print_iterations == 0:
            msg = f"Score at iteration {iteration} is {model.score():.6f}"
            if self._out is not None:
                print(msg, file=self._out)
            else:
                logger.info(msg)


class PerformanceListener(IterationListener):
    """Throughput sampling (reference ``PerformanceListener.java:99-102``):
    iteration time, samples/sec, batches/sec.  These are the numbers BASELINE
    tracks (samples/sec/chip)."""

    def __init__(self, frequency: int = 1, report_score: bool = False,
                 out=None):
        self.frequency = max(1, frequency)
        self.report_score = report_score
        self._out = out
        self._last_time: Optional[float] = None
        self._last_iter: Optional[int] = None
        self.history: List[Tuple[int, float, float]] = []  # (iter, samples/s, batches/s)

    def iteration_done(self, model, iteration: int) -> None:
        now = time.perf_counter()
        if self._last_time is not None and iteration % self.frequency == 0:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            if dt > 0 and iters > 0:
                batch_size = getattr(model, "last_batch_size", None)
                batches_per_sec = iters / dt
                samples_per_sec = (batches_per_sec * batch_size
                                   if batch_size else float("nan"))
                self.history.append((iteration, samples_per_sec,
                                     batches_per_sec))
                msg = (f"iteration {iteration}: {samples_per_sec:.1f} "
                       f"samples/sec, {batches_per_sec:.2f} batches/sec")
                if self.report_score:
                    msg += f", score {model.score():.6f}"
                if self._out is not None:
                    print(msg, file=self._out)
                else:
                    logger.info(msg)
        if iteration % self.frequency == 0:
            self._last_time = now
            self._last_iter = iteration

    def average_samples_per_sec(self, skip: int = 1) -> float:
        """Mean throughput, skipping the first ``skip`` samples (compile)."""
        vals = [s for _, s, _ in self.history[skip:]]
        return float(np.mean(vals)) if vals else float("nan")


class CollectScoresIterationListener(IterationListener):
    """Collect (iteration, score) pairs (reference
    ``CollectScoresIterationListener``)."""

    def __init__(self, frequency: int = 1):
        self.frequency = max(1, frequency)
        self.scores: List[Tuple[int, float]] = []

    def iteration_done(self, model, iteration: int) -> None:
        if iteration % self.frequency == 0:
            self.scores.append((iteration, model.score()))


class ParamAndGradientIterationListener(IterationListener):
    """Per-parameter statistics every N iterations (reference
    ``ParamAndGradientIterationListener.java``: mean, min/max, mean
    absolute value, tab-delimited to console and/or file).

    Gradients are fused inside the jitted train step and never
    materialise host-side, so the reference's gradient columns are
    reported as *update_win* statistics — the parameter delta since this
    listener last ran (a WINDOWED delta, which the column names now say
    explicitly), which is what the updater applied (the same
    substitution the stats listener makes; update:param magnitude ratios
    are the quantity the reference UI derives from these columns anyway).

    When the device-side health layer is enabled
    (``monitor.enable_health()``) two exact per-step columns are
    appended from the packed in-jit stats of the model's last dispatch:
    ``grad_l2_step`` (per-layer gradient L2 norm) and
    ``update_ratio_step`` (per-layer update:param L2 ratio).  Every
    param row of a layer carries its layer's value; blank when the layer
    is not represented in the last health snapshot.
    """

    def __init__(self, iterations: int = 1, print_header: bool = True,
                 print_mean: bool = True, print_min_max: bool = True,
                 print_mean_abs_value: bool = True,
                 output_to_console: bool = True,
                 file_path: Optional[str] = None, delimiter: str = "\t"):
        self.iterations = max(1, iterations)
        self.print_header = print_header
        self.print_mean = print_mean
        self.print_min_max = print_min_max
        self.print_mean_abs = print_mean_abs_value
        self.output_to_console = output_to_console
        self.file_path = file_path
        self.delimiter = delimiter
        self._last_params = None
        self._header_written = False
        if file_path:
            # truncate once; appends follow (reference opens with append
            # after an initial header write)
            # dl4j-lint: disable=R2 append-log truncation, not a final-file write; rows stream in afterwards so rename-into-place has nothing to protect
            open(file_path, "w").close()

    @staticmethod
    def _tables(model):
        if hasattr(model, "param_table"):
            return model.param_table()
        return {}

    @staticmethod
    def _device_stats(model, name):
        """(grad_l2, update_ratio) for this param's layer from the last
        health dispatch, or None when the health layer has nothing."""
        from ...monitor import health as _health
        if not _health.enabled():
            return None
        snap = _health.last_for(model)
        if snap is None:
            return None
        layer = name.rsplit("_", 1)[0]
        stats = snap["layers"].get(layer)
        if stats is None:
            return ("", "")
        return (f"{stats['grad_l2']:.6g}", f"{stats['update_ratio']:.6g}")

    def _stats(self, name, arr, prev, device=None):
        cols = [name]
        if self.print_mean:
            cols.append(f"{float(np.mean(arr)):.6g}")
        if self.print_min_max:
            cols += [f"{float(np.min(arr)):.6g}",
                     f"{float(np.max(arr)):.6g}"]
        if self.print_mean_abs:
            cols.append(f"{float(np.mean(np.abs(arr))):.6g}")
        upd = arr - prev if prev is not None else np.zeros_like(arr)
        if self.print_mean:
            cols.append(f"{float(np.mean(upd)):.6g}")
        if self.print_min_max:
            cols += [f"{float(np.min(upd)):.6g}",
                     f"{float(np.max(upd)):.6g}"]
        if self.print_mean_abs:
            cols.append(f"{float(np.mean(np.abs(upd))):.6g}")
        if device is not None:
            cols += list(device)
        return cols

    def _header(self, with_device=False):
        cols = ["param"]
        for kind in ("param", "update_win"):
            if self.print_mean:
                cols.append(f"{kind}_mean")
            if self.print_min_max:
                cols += [f"{kind}_min", f"{kind}_max"]
            if self.print_mean_abs:
                cols.append(f"{kind}_mean_abs")
        if with_device:
            cols += ["grad_l2_step", "update_ratio_step"]
        return cols

    def _emit(self, line: str) -> None:
        if self.output_to_console:
            logger.info(line)
        if self.file_path:
            with open(self.file_path, "a", encoding="utf-8") as f:
                f.write(line + "\n")

    def iteration_done(self, model, iteration: int) -> None:
        if iteration % self.iterations != 0:
            return
        tables = self._tables(model)
        from ...monitor import health as _health
        with_device = (_health.enabled()
                       and _health.last_for(model) is not None)
        if self.print_header and not self._header_written:
            self._emit(self.delimiter.join(
                ["iteration"] + self._header(with_device)))
            self._header_written = True
        prev = self._last_params or {}
        for name, arr in tables.items():
            device = self._device_stats(model, name) if with_device else None
            cols = self._stats(name, arr, prev.get(name), device)
            self._emit(self.delimiter.join([str(iteration)] + cols))
        self._last_params = tables


def finalize_listeners(listeners) -> None:
    """Run every listener's end-of-training hooks (``stop()`` then
    ``flush()`` where present).  ``fit()`` calls this in a ``finally``
    block so a ``ProfilerListener`` capture opened mid-training is closed
    even when training ends before ``end_iteration`` or raises, and async
    ``CheckpointListener`` writes are joined.  Hook exceptions are logged,
    not raised — finalization must never mask the original fit error."""
    for listener in listeners or ():
        for hook in ("stop", "flush"):
            fn = getattr(listener, hook, None)
            if callable(fn):
                try:
                    fn()
                except Exception:  # pragma: no cover - defensive
                    logging.getLogger(__name__).warning(
                        "listener %s.%s() failed during finalization",
                        type(listener).__name__, hook, exc_info=True)


class ProfilerListener(TrainingListener):
    """jax.profiler hookup (SURVEY.md §5 tracing/profiling): capture a
    device trace for iterations ``[start_iteration, end_iteration)`` into
    ``log_dir`` (viewable in TensorBoard/Perfetto), plus host-side phase
    timings per iteration.  The reference exposes runtime timing through
    PerformanceListener; XLA's profiler is the TPU-native deep-dive
    equivalent.  The capture runs through ``monitor.DeviceTrace``, the
    program's one way to take a trace, so it is reduced when it closes:
    :meth:`device_report` has the device seconds by scope.  Like every
    listener this one breaks ``fit``'s fused dispatch; profile the fused
    path with ``with monitor.device_trace(log_dir): net.fit(...)``."""

    def __init__(self, log_dir: str, start_iteration: int = 2,
                 end_iteration: int = 5):
        from ... import monitor as _monitor
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.end_iteration = end_iteration
        self._trace = _monitor.DeviceTrace(log_dir)
        self._last_t: Optional[float] = None
        self.iteration_times_ms: List[float] = []

    def iteration_done(self, model, iteration: int) -> None:
        now = time.perf_counter()
        if self._last_t is not None:
            self.iteration_times_ms.append((now - self._last_t) * 1e3)
        self._last_t = now
        if not self._trace.open and iteration >= self.start_iteration \
                and iteration < self.end_iteration:
            self._trace.start()
        elif self._trace.open and iteration >= self.end_iteration:
            self._trace.stop()

    def stop(self) -> None:
        """Close a still-open capture (only needed when training ended
        before ``end_iteration``).  Deliberately NOT hooked to epoch
        boundaries — a capture window spanning epochs must stay one
        contiguous trace.  Idempotent: ``DeviceTrace.stop`` closes the
        capture exactly once, swallows a failed ``stop_trace`` on the
        error path, and records the ``profiler/capture`` span."""
        self._trace.stop()

    def device_report(self) -> Optional[dict]:
        """Device seconds by scope and pass of the closed capture
        (``monitor.device_trace.reduce``); ``None`` while it is open and
        where the trace holds no TPU operation (the CPU)."""
        return self._trace.report

    def phase_report(self) -> dict:
        """Host-side phase timing summary (mean/p50/p95 iteration ms)."""
        if not self.iteration_times_ms:
            return {"iterations": 0}
        arr = np.asarray(self.iteration_times_ms)
        return {"iterations": int(arr.size),
                "mean_ms": float(arr.mean()),
                "p50_ms": float(np.percentile(arr, 50)),
                "p95_ms": float(np.percentile(arr, 95))}


class CheckpointListener(TrainingListener):
    """Periodic training checkpoints with retention and async writes
    (the later-reference ``CheckpointListener``; at 0.7.3 the only
    checkpointing is the early-stopping savers, so this is the
    iteration-frequency tier a long TPU run needs).

    Every ``save_every_n_iterations`` iterations (or at every epoch end
    with ``save_every_epochs``), the FULL training state — conf, params,
    updater state (``ModelSerializer`` zip, so ``restore_*`` resumes
    bit-exactly) — is written to ``checkpoint_<iter>.zip`` in ``dir``.
    Writes go tmpfile-then-atomic-rename, so a crash mid-write never
    corrupts the latest checkpoint; ``keep_last`` bounds disk use;
    ``async_write=True`` serializes on the calling thread (params are
    fetched synchronously — tiny vs a TPU step) but does file IO on a
    background thread so the training loop never blocks on disk."""

    def __init__(self, checkpoint_dir: str,
                 save_every_n_iterations: int = 0,
                 save_every_epochs: int = 0, keep_last: int = 3,
                 async_write: bool = True):
        import os
        if save_every_n_iterations <= 0 and save_every_epochs <= 0:
            raise ValueError("set save_every_n_iterations and/or "
                             "save_every_epochs")
        self.dir = checkpoint_dir
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.every_iter = int(save_every_n_iterations)
        self.every_epochs = int(save_every_epochs)
        self.keep_last = max(1, int(keep_last))
        self.async_write = async_write
        self._epoch = 0
        self._last_saved_iter = None   # both triggers firing on one
        self._pending: dict = {}       # path -> writer thread
        self._write_errors: list = []  # (path, exception)
        self.saved: list = []          # checkpoint paths, oldest first

    # ------------------------------------------------------------- hooks
    def iteration_done(self, model, iteration: int) -> None:
        if self.every_iter > 0 and iteration % self.every_iter == 0:
            self._save(model, iteration)

    def on_epoch_end(self, model) -> None:
        self._epoch += 1
        if self.every_epochs > 0 and self._epoch % self.every_epochs == 0:
            self._save(model, model.iteration)

    # ------------------------------------------------------------- write
    def _save(self, model, iteration: int) -> None:
        import io
        import os
        import threading

        from ...utils.fileio import atomic_write_bytes
        from ...utils.model_serializer import write_model

        if iteration == self._last_saved_iter:
            return      # iteration AND epoch trigger fired together
        self._last_saved_iter = iteration

        # serialize NOW (state snapshot) ...
        buf = io.BytesIO()
        write_model(model, buf)
        data = buf.getvalue()
        path = os.path.join(self.dir, f"checkpoint_{iteration}.zip")

        def write():
            try:
                # atomic_write mkstemps its own unique tmp, so two
                # checkpoints of the SAME iteration in one listener
                # lifetime (restore+retrain, fit after iteration reset)
                # never interleave partial writes on one tmp file
                atomic_write_bytes(path, data)
            except BaseException as e:  # surfaced by flush()
                self._write_errors.append((path, e))

        if self.async_write:
            prior = self._pending.get(path)
            if prior is not None:
                prior.join()     # same-path re-write: serialize, last wins
            t = threading.Thread(target=write, daemon=True)
            t.start()
            self._pending[path] = t
        else:
            write()
            self._raise_write_errors()
        if path in self.saved:       # re-checkpointed iteration: keep one
            self.saved.remove(path)  # retention slot, refresh recency
        self.saved.append(path)
        while len(self.saved) > self.keep_last:
            old = self.saved.pop(0)
            # join ONLY the evicted checkpoint's writer (it finished long
            # ago in steady state) — joining everything would serialize
            # the write we just started
            t = self._pending.pop(old, None)
            if t is not None:
                t.join()
            try:
                os.remove(old)
            except OSError:
                pass

    def _raise_write_errors(self) -> None:
        if self._write_errors:
            path, err = self._write_errors[0]
            self._write_errors = []
            raise RuntimeError(
                f"checkpoint write failed for {path}") from err

    def flush(self) -> None:
        """Join outstanding async writes; raises if any write failed
        (a silently lost checkpoint would surface as FileNotFoundError
        at resume time, far from the real cause)."""
        for t in self._pending.values():
            t.join()
        self._pending = {}
        self._raise_write_errors()

    def last_checkpoint(self) -> "str | None":
        self.flush()
        return self.saved[-1] if self.saved else None
