"""Training callbacks.

Reference: ``python/mxnet/callback.py`` — module_checkpoint,
do_checkpoint, log_train_metric, Speedometer, ProgressBar,
LogValidationMetricsCallback.

Log-format contract: the ``Epoch[%d] ... Speed: ... samples/sec``,
``Train-<metric>=``, ``Validation-<metric>=`` and ``Time cost=`` line
shapes are machine-parsed (tools/parse_log.py and the reference's
own tooling) and must not be reworded; everything else here
is free-form.
"""
from __future__ import annotations

import logging
import time

__all__ = ["module_checkpoint", "do_checkpoint", "log_train_metric",
           "Speedometer", "ProgressBar", "LogValidationMetricsCallback"]


def module_checkpoint(mod, prefix=None, period=1, save_optimizer_states=False,
                      manager=None):
    """Checkpoint a module every `period` epochs (reference: callback.py:28).

    ``period`` counts from the last SUCCESSFUL save: a failed or
    refused save (disk error, async writer busy) is retried at the next
    epoch instead of silently waiting another full period — the old
    modulo schedule could stretch the gap between durable snapshots to
    ``2*period - 1`` epochs after one bad epoch.

    ``manager``: route saves through a ``checkpoint.CheckpointManager``
    (atomic, sharded, full resume state) instead of — when ``prefix``
    is None — or in addition to the legacy prefix files."""
    period = int(max(1, period))
    if prefix is None and manager is None:
        raise ValueError("module_checkpoint needs a prefix, a manager, "
                         "or both")
    last_saved = [0]   # epochs completed at the last successful save

    def _callback(iter_no, sym=None, arg=None, aux=None):
        done = iter_no + 1
        if done - last_saved[0] < period:
            return
        try:
            if manager is not None:
                if not manager.save_module(mod, epoch=done):
                    return   # writer busy — retry next epoch
                if prefix is not None:
                    # manager=False: the managed save just happened —
                    # don't let MXNET_CKPT_DIR route a second one
                    mod.save_checkpoint(prefix, done, save_optimizer_states,
                                        manager=False)
            else:
                mod.save_checkpoint(prefix, done, save_optimizer_states)
        except Exception:
            logging.warning("checkpoint at epoch %d failed; retrying next "
                            "epoch", done, exc_info=True)
            return
        last_saved[0] = done
    return _callback


def do_checkpoint(prefix, period=1):
    """Checkpoint params every `period` epochs (reference: callback.py:56)."""
    from .model import save_checkpoint
    period = int(max(1, period))

    def _callback(iter_no, sym, arg, aux):
        if (iter_no + 1) % period == 0:
            save_checkpoint(prefix, iter_no + 1, sym, arg, aux)
    return _callback


def log_train_metric(period, auto_reset=False):
    """Log metric every `period` batches (reference: callback.py:84)."""

    def _callback(param):
        if param.nbatch % period == 0 and param.eval_metric is not None:
            for name, value in param.eval_metric.get_name_value():
                logging.info("Iter[%d] Batch[%d] Train-%s=%f",
                             param.epoch, param.nbatch, name, value)
            if auto_reset:
                param.eval_metric.reset()
    return _callback


class Speedometer:
    """Log samples/sec and metrics periodically (reference: callback.py:115).

    The emitted line shape is part of the log-format contract above.
    """

    def __init__(self, batch_size, frequent=50, auto_reset=True):
        self.batch_size = batch_size
        self.frequent = frequent
        self.auto_reset = auto_reset
        self._tick = None
        self._last_count = 0

    def __call__(self, param):
        count = param.nbatch
        if count < self._last_count:       # new epoch restarts the window
            self._tick = None
        self._last_count = count
        if self._tick is None:
            self._tick = time.time()
            return
        if count % self.frequent:
            return
        # reading the metric value drains the device queue (device-side
        # accumulation is lazy), so the window measures completed work,
        # not the host's async enqueue rate
        metric_parts = []
        if param.eval_metric is not None:
            for name, value in param.eval_metric.get_name_value():
                metric_parts.append("%s=%f" % (name, value))
            if self.auto_reset:
                param.eval_metric.reset()
        speed = self.frequent * self.batch_size / (time.time() - self._tick)
        from . import telemetry
        if telemetry.enabled():
            telemetry.gauge(
                "mxnet_speed_samples_per_sec",
                "Speedometer window throughput").set(round(speed, 3))
        head = ("Epoch[%d]" % param.epoch) if metric_parts \
            else ("Iter[%d]" % param.epoch)
        logging.info("\t".join(
            ["%s Batch [%d]" % (head, count),
             "Speed: %.2f samples/sec" % speed] + metric_parts))
        self._tick = time.time()


class ProgressBar:
    """ASCII progress bar (reference: callback.py:187)."""

    def __init__(self, total, length=80):
        self.bar_len = length
        self.total = total

    def __call__(self, param):
        frac = param.nbatch / float(self.total)
        filled = int(round(self.bar_len * frac))
        bar = "=" * filled + "-" * (self.bar_len - filled)
        logging.info("[%s] %d%%\r", bar, int(frac * 100 + 0.999))


class LogValidationMetricsCallback:
    """Log validation metrics at epoch end (reference: callback.py:211;
    line shape is contract — see module docstring)."""

    def __call__(self, param):
        if not param.eval_metric:
            return
        for name, value in param.eval_metric.get_name_value():
            logging.info("Epoch[%d] Validation-%s=%f",
                         param.epoch, name, value)
