"""The last line of a run: built in one place and checked against the
contract before it is printed.

``emit`` either prints one JSON object (and nothing after it) or raises
``BadResultLine``; ``run.py`` turns that into a non-zero exit with the reason
on stderr and no result line. The checks are the driver's own reading of the
line: the five keys, every metric the manifest lists for this cell and run
type as a finite number with its unit, ``device`` with platform, kind, count
and memory_peak_bytes, and in a traced run ``0 < busy_s <= window_s``.
"""

from __future__ import annotations

import json
import math
import sys

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")


class BadResultLine(ValueError):
    """The line as built would not be read by the driver."""


def _finite(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def expected_metrics(manifest: dict, cell: str, traced: bool) -> dict:
    """{name: unit} of the metrics the manifest promises for ``cell`` in a
    ``--trace 0`` run (end-to-end) or a ``--trace 1`` run (per-layer).

    An end-to-end metric without ``workloads`` belongs to every cell; a
    per-layer metric without it belongs to every cell that reports the
    end-to-end metric it ``moves``."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])}
    if not traced:
        return {name: m["unit"] for name, m in e2e.items()}
    return {m["name"]: m["unit"] for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)}


def validate(line: dict, expected: dict, *, traced: bool, chips: int) -> None:
    """Raise ``BadResultLine`` unless ``line`` is what the driver reads."""
    for key in KEYS:
        if key not in line:
            raise BadResultLine(f"key {key!r} is missing")
    if not isinstance(line["correct"], bool):
        raise BadResultLine("correct is not true or false")
    for key in ("attempted", "failed"):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            raise BadResultLine(f"{key} is not a count: {v!r}")
    if line["attempted"] < 1:
        raise BadResultLine("nothing was attempted in the window")
    if line["failed"] > line["attempted"]:
        raise BadResultLine("more failed than attempted")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        raise BadResultLine("metrics is not an object")
    for name, unit in expected.items():
        if name not in metrics:
            raise BadResultLine(f"metric {name!r} is missing")
    for name, m in metrics.items():
        if name not in expected:
            raise BadResultLine(
                f"metric {name!r} is not one the manifest lists for this "
                "cell and run type")
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            raise BadResultLine(f"metric {name!r} lacks value or unit")
        if not _finite(m["value"]):
            raise BadResultLine(
                f"metric {name!r} is not a finite number: {m['value']!r}")
        if m["unit"] != expected[name]:
            raise BadResultLine(
                f"metric {name!r} has unit {m['unit']!r}, the manifest "
                f"says {expected[name]!r}")

    device = line["device"]
    if not isinstance(device, dict):
        raise BadResultLine("device is not an object")
    for key in DEVICE_KEYS:
        if key not in device:
            raise BadResultLine(f"device.{key} is missing")
    if not isinstance(device["platform"], str) or not device["platform"]:
        raise BadResultLine("device.platform is not a name")
    if not isinstance(device["kind"], str) or not device["kind"]:
        raise BadResultLine("device.kind is not a name")
    if device["count"] != chips:
        raise BadResultLine(
            f"device.count {device['count']!r} is not the cell's {chips}")
    if not _finite(device["memory_peak_bytes"]) or device["memory_peak_bytes"] <= 0:
        raise BadResultLine(
            f"device.memory_peak_bytes {device['memory_peak_bytes']!r}")
    if traced:
        for key in ("busy_s", "window_s"):
            if not _finite(device.get(key)):
                raise BadResultLine(
                    f"device.{key} is not a finite number: {device.get(key)!r}")
        if not 0.0 < device["busy_s"] <= device["window_s"]:
            raise BadResultLine(
                f"device.busy_s {device['busy_s']!r} is not above 0 and at "
                f"most window_s {device['window_s']!r}")

    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            raise BadResultLine("breakdown has keys other than "
                                f"{BREAKDOWN_KEYS}")
        for key, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise BadResultLine(f"breakdown.{key} is not a list of at "
                                    "most 10 entries")
            for row in rows:
                if (not isinstance(row, (list, tuple)) or len(row) != 2
                        or not isinstance(row[0], str) or not _finite(row[1])):
                    raise BadResultLine(
                        f"breakdown.{key} entry {row!r} is not [name, seconds]")
    try:
        text = json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise BadResultLine(f"not JSON: {e}") from e
    if "\n" in text:
        raise BadResultLine("the line holds a newline")


def build(*, correct: bool, attempted: int, failed: int, values: dict,
          units: dict, device: dict, breakdown: dict | None = None) -> dict:
    """The line from its parts. ``values`` maps metric name → number; a
    reader that found nothing gave no entry, and ``validate`` then says
    which metric is missing."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": values[name], "unit": units[name]}
                    for name in values if name in units},
        "device": device,
    }
    extra = sorted(set(values) - set(units))
    if extra:
        raise BadResultLine(f"values for metrics the manifest does not list "
                            f"for this cell: {extra}")
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def emit(line: dict, expected: dict, *, traced: bool, chips: int,
         out=None) -> None:
    """Validate, then print as the last line of stdout and flush."""
    validate(line, expected, traced=traced, chips=chips)
    out = out or sys.stdout
    out.write(json.dumps(line, allow_nan=False) + "\n")
    out.flush()
