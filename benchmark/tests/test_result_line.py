"""``result_line`` accepts a good line and refuses each malformed one."""

import copy
import io
import json

import pytest

from benchmark import result_line as rl

EXPECTED_E2E = {"train_img_per_s": "img/s", "setup_s": "s"}
EXPECTED_TRACED = {"train_mfu": "%", "allreduce_time_share": "%"}


def good(traced: bool, chips: int = 4) -> dict:
    metrics = ({"train_mfu": {"value": 11.5, "unit": "%"},
                "allreduce_time_share": {"value": 1.25, "unit": "%"}}
               if traced else
               {"train_img_per_s": {"value": 412.7, "unit": "img/s"},
                "setup_s": {"value": 41.2, "unit": "s"}})
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
              "memory_peak_bytes": 5_000_000_000}
    line = {"correct": True, "attempted": 120, "failed": 0,
            "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=3.1, window_s=4.0)
        line["breakdown"] = {"device_ops": [["fwd", 1.2]],
                             "idle_gaps": [["bench/next_batch", 0.4]]}
    return line


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_is_printed_last(traced):
    out = io.StringIO()
    expected = EXPECTED_TRACED if traced else EXPECTED_E2E
    rl.emit(good(traced), expected, traced=traced, chips=4, out=out)
    text = out.getvalue()
    assert text.endswith("\n") and text.count("\n") == 1
    assert json.loads(text)["device"]["count"] == 4


def _drop(path):
    def f(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        del node[path[-1]]
    return f


def _set(path, value):
    def f(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return f


MALFORMED = {
    "missing key": (True, _drop(["device"])),
    "missing metric": (True, _drop(["metrics", "allreduce_time_share"])),
    "None metric": (True, _set(["metrics", "train_mfu", "value"], None)),
    "NaN metric": (True, _set(["metrics", "train_mfu", "value"], float("nan"))),
    "infinite metric": (False, _set(["metrics", "train_img_per_s", "value"],
                                    float("inf"))),
    "missing unit": (False, _drop(["metrics", "setup_s", "unit"])),
    "wrong unit": (False, _set(["metrics", "setup_s", "unit"], "ms")),
    "unlisted metric": (False, _set(["metrics", "extra"],
                                    {"value": 1.0, "unit": "s"})),
    "busy_s 0": (True, _set(["device", "busy_s"], 0.0)),
    "busy_s above window_s": (True, _set(["device", "busy_s"], 4.5)),
    "busy_s summed over chips": (True, _set(["device", "busy_s"], 12.4)),
    "busy_s missing": (True, _drop(["device", "busy_s"])),
    "window_s None": (True, _set(["device", "window_s"], None)),
    "count of another cell": (False, _set(["device", "count"], 1)),
    "no memory peak": (False, _drop(["device", "memory_peak_bytes"])),
    "correct not a bool": (False, _set(["correct"], "true")),
    "nothing attempted": (False, _set(["attempted"], 0)),
    "more failed than attempted": (False, _set(["failed"], 121)),
    "breakdown too long": (True, _set(["breakdown", "device_ops"],
                                      [["op", 0.1]] * 11)),
    "breakdown entry NaN": (True, _set(["breakdown", "idle_gaps"],
                                       [["x", float("nan")]])),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_line_is_refused_and_nothing_printed(case):
    traced, damage = MALFORMED[case]
    line = copy.deepcopy(good(traced))
    damage(line)
    out = io.StringIO()
    expected = EXPECTED_TRACED if traced else EXPECTED_E2E
    with pytest.raises(rl.BadResultLine):
        rl.emit(line, expected, traced=traced, chips=4, out=out)
    assert out.getvalue() == ""


def test_expected_metrics_follow_the_manifest(toy_manifest):
    e2e = rl.expected_metrics(toy_manifest, "toy_train_dp4", False)
    assert e2e == {"train_img_per_s": "img/s", "setup_s": "s"}
    traced = rl.expected_metrics(toy_manifest, "toy_train_dp4", True)
    assert traced == {"train_mfu": "%"}  # no `workloads`: follows `moves`
    assert rl.expected_metrics(toy_manifest, "toy_serve", True) == {
        "serve_padded_row_share": "%"}


def test_a_reader_that_found_nothing_makes_the_line_invalid():
    line = rl.build(correct=True, attempted=3, failed=0,
                    values={"train_mfu": 9.0}, units=EXPECTED_TRACED,
                    device=good(True)["device"])
    with pytest.raises(rl.BadResultLine, match="allreduce_time_share"):
        rl.validate(line, EXPECTED_TRACED, traced=True, chips=4)
