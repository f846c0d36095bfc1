"""utils/logging.ScalarWriter: the event file it writes itself, record by
record, and what a fresh interpreter does and does not import for it."""

import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from ddim_cold_tpu.utils.logging import ScalarWriter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(path):
    """TFRecord framing read back by hand: u64 length, masked CRC of the
    length, the bytes, masked CRC of the bytes — both CRCs checked."""
    from tensorboard.compat.tensorflow_stub.pywrap_tensorflow import masked_crc32c

    raw = open(path, "rb").read()
    at = 0
    while at < len(raw):
        header = raw[at:at + 8]
        (length,) = struct.unpack("<Q", header)
        assert struct.unpack("<I", raw[at + 8:at + 12])[0] == masked_crc32c(header)
        data = raw[at + 12:at + 12 + length]
        assert len(data) == length
        assert struct.unpack("<I", raw[at + 12 + length:at + 16 + length])[0] == masked_crc32c(data)
        yield data
        at += 16 + length
    assert at == len(raw)


@pytest.mark.parametrize("rows", [
    [("loss", 0.25, 0)],
    [("loss", 0.5, 0), ("lr", 1e-3, 0), ("loss", 0.4, 1), ("lr", 9e-4, 1), ("ema/loss", 0.45, 1)],
    [("loss", 1.0, 7), ("loss", 2.0, 7), ("loss", 1 / 3, 8)],
], ids=["one_scalar", "tags_interleaved", "step_repeats"])
def test_event_file_reads_back_record_by_record(tmp_path, rows):
    from tensorboard.compat.proto.event_pb2 import Event

    writer = ScalarWriter(str(tmp_path))
    for tag, value, step in rows:
        writer.add_scalar(tag, value, step)
    # flushed a write: a reader sees every row before close()
    (name,) = [f for f in os.listdir(tmp_path) if f.startswith("events.out.tfevents.")]
    events = [Event.FromString(r) for r in _records(tmp_path / name)]
    writer.close()
    writer.close()  # a second close is harmless, and closing added nothing
    assert [Event.FromString(r) for r in _records(tmp_path / name)] == events

    stamp, host = name[len("events.out.tfevents."):].split(".", 1)
    assert stamp.isdigit() and host
    assert events[0].file_version == "brain.Event:2"
    got = [(e.summary.value[0].tag, e.summary.value[0].simple_value, e.step) for e in events[1:]]
    assert all(len(e.summary.value) == 1 for e in events[1:])
    assert got == [(tag, float(np.float32(value)), step) for tag, value, step in rows]
    walls = [e.wall_time for e in events]
    assert walls == sorted(walls) and walls[0] > 0

    jsonl = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    assert [(r["tag"], r["value"], r["step"]) for r in jsonl] == rows


def _child(code, tmp_path):
    return subprocess.run([sys.executable, "-c", code, str(tmp_path)], cwd=ROOT, text=True,
                          capture_output=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_writer_and_trainer_import_neither_torch_nor_tensorflow(tmp_path):
    out = _child(
        "import sys\n"
        "from ddim_cold_tpu.utils.logging import ScalarWriter\n"
        "w = ScalarWriter(sys.argv[1]); w.add_scalar('a', 1.0, 0); w.close()\n"
        "import ddim_cold_tpu.train.trainer\n"
        "print(sorted(m for m in ('tensorflow', 'keras', 'torch') if m in sys.modules))\n",
        tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
    assert any(f.startswith("events.out.tfevents.") for f in os.listdir(tmp_path))


def test_without_tensorboard_the_jsonl_is_kept_and_no_event_file_written(tmp_path):
    out = _child(
        "import sys\n"
        "sys.modules['tensorboard'] = None\n"
        "from ddim_cold_tpu.utils.logging import ScalarWriter\n"
        "w = ScalarWriter(sys.argv[1]); w.add_scalar('a', 1.0, 0); w.add_scalar('a', 2.0, 1); w.close()\n",
        tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.listdir(tmp_path) == ["metrics.jsonl"]
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    assert [(r["tag"], r["value"], r["step"]) for r in rows] == [("a", 1.0, 0), ("a", 2.0, 1)]
