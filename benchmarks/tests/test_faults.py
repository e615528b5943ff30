"""A whole run of a cell, with the program's timed path broken underneath,
has to come out with ``correct`` false; unbroken, true.  The control (the
reference contracted in float32, put in the program's place as server 0)
has to come out not correct under the same comparison and limits.

Each fault is planted in ``DPF._dispatch_packed``, the device dispatch
under both ``DPF.eval_tpu`` and ``ServingEngine.submit``:

* ``altered``: every answer's first word is off by one where it is
  produced;
* ``half``: half of each batch left out, its rows answered with the
  other half's;
* ``stale``: each dispatch answers with the previous one's shares, as a
  step that returns its state unchanged would.

Each is run again with the reference on the devices
(``check.reference_on``), whose shares the comparison reads the same
way.

The one-key open-loop cell cannot lose half a batch: its one real key
sits first in every padded dispatch.  No cell spans chips, so none can
leave out an exchange between them.
"""

import os
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests.conftest import cpu_device, edit_json

CELLS = ("chacha20-n16.batch512", "chacha20-n16.open1")


def _altered(orig):
    def f(self, pk):
        return orig(self, pk).at[:, 0].add(1)
    return f


def _half(orig):
    def f(self, pk):
        out = orig(self, pk)
        h = out.shape[0] // 2
        return out.at[h:2 * h].set(out[:h])
    return f


def _stale(orig):
    last = []

    def f(self, pk):
        out = orig(self, pk)
        prev = last[0] if last and last[0].shape == out.shape else out
        last[:] = [out]
        return prev
    return f


FAULTS = {"altered": _altered, "half": _half, "stale": _stale}


def run(root, cell, seconds=1.0, **kw):
    return harness.run_cell(cell, 2 ** 33 + 17, seconds, False,
                            time.monotonic(), root=root, look=cpu_device,
                            **kw)


def sound_run_is_correct_and_control_fails(root, cell):
    out = run(root, cell, control=True)
    assert out["correct"] is True
    assert all(v["value"] == 0 for v in out["compared"].values())
    ctrl = out["control"]
    assert ctrl["correct"] is False
    assert ctrl["compared"].keys() == out["compared"].keys()
    # the control fails each number on every sampled key
    assert all(v["value"] > v["limit"] for v in ctrl["compared"].values())
    assert list(out)[-2:] == ["control", "compared"]


def broken_timed_path_is_not_correct(root, monkeypatch, cell, fault):
    if fault == "half" and cell.endswith("open1"):
        pytest.skip("one key per request: no half batch to lose")
    from dpf_tpu.api import DPF
    monkeypatch.setattr(DPF, "_dispatch_packed",
                        FAULTS[fault](DPF._dispatch_packed))
    out = run(root, cell)
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_fails(tiny_root, cell):
    sound_run_is_correct_and_control_fails(tiny_root, cell)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell, fault)


def test_device_reference_sound_run_is_correct_and_control_fails(
        tiny_devices_root):
    sound_run_is_correct_and_control_fails(tiny_devices_root,
                                           "chacha20-n16.batch512")


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_device_reference_broken_timed_path_is_not_correct(
        tiny_devices_root, monkeypatch, fault):
    broken_timed_path_is_not_correct(tiny_devices_root, monkeypatch,
                                     "chacha20-n16.batch512", fault)


@pytest.mark.parametrize("place,prf", [("devices", "aes128"),
                                       ("tpu", "chacha20")])
def test_a_reference_that_cannot_run_exits_before_setup(
        tmp_path, monkeypatch, place, prf):
    """A configuration whose reference cannot run where it says exits
    with a message before the look for a chip and the table."""
    from benchmarks.tests.conftest import make_tiny_root
    root = make_tiny_root(str(tmp_path), reference_on=place)
    cfg = os.path.join(root, "benchmarks", "configs",
                       "ref-chacha20-n16.json")
    edit_json(cfg, prf=prf)

    def no_setup(*a):
        raise AssertionError("set-up began")

    monkeypatch.setattr(harness, "make_table", no_setup)
    with pytest.raises(SystemExit, match="reference_on"):
        harness.run_cell("chacha20-n16.batch512", 1, 1.0, False,
                         time.monotonic(), root=root, look=no_setup)


def test_result_line_keys(tiny_root):
    out = run(tiny_root, "chacha20-n16.batch512")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"dpfs_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert np.isfinite(out["metrics"]["dpfs_per_s"]["value"])
