"""Test of the reader ``chipbench/layer_metrics/sw_copy_share.py`` on
synthetic traces whose sums are known.  No time is asserted here that a chip
would give: the numbers are the hand-made cases'.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import harness, trace_reduce, work  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
MS = 1_000_000
FIELDS = "(f32[28802,3602]{1,0:T(8,128)}, f32[28802,3602]{1,0:T(8,128)})"
EULER = f"%sw_steps_x1_euler.1 = {FIELDS} custom-call(f32[28802,3602] %b)"
PAIR = f"%sw_steps_x2.3 = {FIELDS} custom-call(f32[28802,3602] %a)"
PAIR_B = f"%sw_steps_x2.4 = {FIELDS} custom-call(f32[28802,3602] %c)"
COPY = "%copy.7 = f32[28802,3602]{1,0:T(8,128)} copy(f32[28802,3602] %h)"
LAYOUT = ("%copy.1 = f32[1,28802,3602]{1,0,2:T(1,128)} "
          "copy(f32[1,28802,3602] %p)")
LOOP = "%while = (s32[], f32[28802,3602]{1,0}) while((s32[]) %tuple)"
# a fusion is not a copy, whatever its name
RESCALE = "%copy_bitcast_fusion.2 = f32[28802,3602]{1,0} fusion(f32[] %y)"


def _legs(form):
    """Two legs of 100 ms on each device, each: a layout copy in (2 ms), the
    Euler-step kernel (8 ms), a loop of 72 ms, a layout copy out (2 ms),
    and a fusion after the legs' spans that no leg holds."""
    device = []
    for leg in (0, 200 * MS):
        device += [(LAYOUT, leg + 1 * MS, 2 * MS),
                   (EULER, leg + 3 * MS, 8 * MS),
                   (LOOP, leg + 11 * MS, 72 * MS),
                   (LAYOUT, leg + 83 * MS, 2 * MS)]
        for i in range(3):
            at = leg + (11 + 24 * i) * MS
            if form == "one_call_an_iteration":
                # the kernel, then the carry copied back: 4 ms of 24
                device += [(PAIR, at, 20 * MS), (COPY, at + 20 * MS, 4 * MS)]
            else:
                device += [(PAIR, at, 12 * MS),
                           (PAIR_B, at + 12 * MS, 12 * MS)]
    device.append((RESCALE, 150 * MS, 5 * MS))
    host = [(trace_reduce.WINDOW_SPAN, 0, 400 * MS)]
    for leg in (0, 200 * MS):
        host += [("dispatch_leg", leg, 1 * MS),
                 ("wait_leg", leg + 1 * MS, 99 * MS)]
    return {"devices": {0: device, 1: list(device)}, "host": host}


def _read(raw):
    ctx = {"trace": trace_reduce.reduce_events(raw), "config": {},
           "traffic": {}, "peaks": {}, "chips": 1, "work": work,
           "reduce": trace_reduce, "counters": {}}
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", "sw_copy_share.py")).read(ctx)


@pytest.mark.parametrize("form,copy_ms", [
    ("one_call_an_iteration", 2 + 3 * 4 + 2),   # of 84 ms busy a leg
    ("two_calls_an_iteration", 2 + 2),
])
def test_copy_share_of_the_legs_busy_time(form, copy_ms):
    """Copies inside the legs over the busy time inside them (84 ms a leg:
    the ``while`` encloses its operations and is not counted twice; the
    fusion between the legs is in neither sum), averaged over the devices."""
    assert _read(_legs(form)) == pytest.approx(100 * copy_ms / 84)


@pytest.mark.parametrize("missing", ["device", "leg"])
def test_copy_share_reports_nothing_without_a_device_or_a_leg(missing):
    raw = _legs("one_call_an_iteration")
    if missing == "device":
        raw["devices"] = {}
    else:
        raw["host"] = [h for h in raw["host"] if not h[0].endswith("_leg")]
    assert _read(raw) is None
