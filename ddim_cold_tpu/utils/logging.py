"""Logging/metrics — train.log is the parity artifact (SURVEY.md C21).

``print_log`` reproduces the reference's append-only logger
(multi_gpu_trainer.py:18-23) and the trainer emits the same line formats:

    Date: <asctime>
    TrainSet batchs:<n> / TestSet batchs:<n>
    steps: {steps:8d} loss: {ema:.4f} time_cost: {secs:.2f}
    epoch: {epoch:4d}    loss: {vloss:.5f}    time:<asctime>

``ScalarWriter`` replaces the rank-0 TensorBoard writer
(multi_gpu_trainer.py:15,108,151): it always appends machine-readable
``metrics.jsonl`` next to the log (so headless TPU runs keep observability
without the TB dependency) and, when the ``tensorboard`` package imports,
writes the same scalars into an event file itself — no torch, no TensorFlow.
"""

from __future__ import annotations

import json
import os
import socket
import time


def print_log(string: str, file_name: str) -> int:
    """Append one line (reference printLog, multi_gpu_trainer.py:18-23)."""
    with open(file_name, "a") as f:
        f.write(string + "\n")
    return 0


def asctime() -> str:
    return time.asctime(time.localtime(time.time()))


class ScalarWriter:
    """add_scalar → metrics.jsonl (always) + a TensorBoard event file (when
    the ``tensorboard`` package imports; the ``tb`` extra suffices).

    The event file is written here: ``tensorboard``'s ``RecordWriter`` (the
    TFRecord framing) over a plain ``open``, one ``file_version`` event and
    then one ``Event`` a scalar. torch's writer and tensorboard's own
    ``EventFileWriter`` both import TensorFlow where it is installed — 16 s
    at every ``trainer.run`` to append a float to a file.
    """

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)
        self.jsonl_path = os.path.join(log_dir, "metrics.jsonl")
        self._tb = None
        try:
            from tensorboard.compat.proto import event_pb2, summary_pb2
            from tensorboard.summary.writer.record_writer import RecordWriter
        except ImportError:  # optional dep; jsonl logging carries on
            return
        self._event, self._summary = event_pb2.Event, summary_pb2.Summary
        self._tb = RecordWriter(open(os.path.join(
            log_dir, f"events.out.tfevents.{int(time.time())}.{socket.gethostname()}"), "wb"))
        self._write_event(file_version="brain.Event:2")

    def _write_event(self, **fields) -> None:
        self._tb.write(self._event(wall_time=time.time(), **fields).SerializeToString())
        self._tb.flush()  # a few scalars an epoch: a reader sees each at once

    def add_scalar(self, tag: str, value: float, step: int) -> None:
        with open(self.jsonl_path, "a") as f:
            f.write(json.dumps({"tag": tag, "value": float(value), "step": int(step),
                                "time": time.time()}) + "\n")
        if self._tb is not None:
            self._write_event(step=int(step), summary=self._summary(
                value=[self._summary.Value(tag=tag, simple_value=float(value))]))

    def close(self) -> None:
        if self._tb is not None and not self._tb.closed:
            self._tb.close()
