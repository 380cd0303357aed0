"""The reduction from a device trace to numbers, on hand-made
intervals (arithmetic) and on small traces recorded on the chip
(`fixtures/`; `record_fixture.py` says how each was made)."""
import os

import pytest

from perfbench.harness import xplane
from perfbench.harness.xplane import Trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIXTURE = os.path.join(FIXTURES, "v5e_4chips.xplane.pb")


def test_merge_and_subtract():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        [0, 4], [5, 7]]
    assert xplane.total(xplane.merge([(0, 2), (1, 3)])) == 3
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    assert xplane.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert xplane.subtract([[0, 4]], []) == [[0, 4]]
    assert xplane.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == [(3, 5), (8, 10)]


def _two_chips():
    # chip 0: busy 0-40 and 60-100 (a fusion inside a while), idle 40-60
    # chip 1: busy 0-100 except 90-100
    return Trace(devices={
        0: [("while.1", 0, 40), ("fusion.1", 0, 30), ("fusion.2", 30, 40),
            ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)", 60, 80),
            ("fusion.3", 70, 100)],
        1: [("fusion.1", 0, 50), ("all-reduce-start.1", 50, 55),
            ("fusion.9", 55, 85), ("all-reduce-done.1", 85, 90)],
    }, host=[("bench:window", 0, 100), ("bench:loss read", 35, 65),
             ("bench:model(x, y)", 65, 70)])


def test_busy_union_and_idle_share():
    tr = _two_chips()
    w0, w1 = xplane.window(tr)
    assert (w0, w1) == (0, 100)
    busy = xplane.busy_by_chip(tr, w0, w1)
    assert busy[0] == pytest.approx(80e-9) and busy[1] == pytest.approx(90e-9)
    assert xplane.idle_pct(tr, w0, w1) == pytest.approx(15.0)
    # a narrower window clips the events
    assert xplane.idle_pct(tr, 50, 100) == pytest.approx(
        100 * (1 - (40 + 40) / 2 / 50))


def test_window_falls_back_to_the_device_events():
    tr = _two_chips()
    tr.host = []
    assert xplane.window(tr) == (0, 100)


def test_exposed_collective_is_what_nothing_else_covers():
    tr = _two_chips()
    # chip 0: all-reduce 60-80, fusion.3 covers 70-80 -> 10 exposed
    # chip 1: start 50-55 and done 85-90, nothing else runs -> 10 exposed
    assert xplane.collective_exposed_pct(tr, 0, 100) == pytest.approx(10.0)
    none = Trace(devices={0: [("fusion.1", 0, 10)]})
    assert xplane.collective_exposed_pct(none, 0, 10) is None


def test_self_time_counts_no_nanosecond_twice():
    ops = [("while.1", 0, 40), ("fusion.1", 0, 30), ("fusion.2", 30, 40),
           ("copy.1", 50, 60)]
    assert xplane.self_times(ops) == [0, 30, 10, 10]
    top = dict(xplane.top_ops(_two_chips(), 0, 100, 10))
    assert top["fusion.1"] == pytest.approx((30 + 50) / 2 / 1e9)
    assert top["while.1"] == 0
    assert len(xplane.top_ops(_two_chips(), 0, 100, 3)) == 3


@pytest.mark.parametrize("name,op", [
    ("%all-reduce.721 = (bf16[64,3,7,7]{3,2,1,0}, f32[64]{0}) all-reduce("
     "bf16[64,3,7,7]{3,2,1,0} %a, f32[64]{0} %b), channel_id=1", "all-reduce"),
    ("%psum.7 = bf16[8]{0:T(8)S(1)} all-reduce(bf16[8]{0} %x)", "all-reduce"),
    ("%ars = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %x)",
     "all-reduce-start"),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop",
     "fusion"),
    ("all-gather-done.2", "all-gather-done"), ("fusion.9", "fusion")])
def test_opcode_not_instruction_name_says_what_is_a_collective(name, op):
    assert xplane.opcode(name) == op
    assert xplane.is_collective(name) is op.startswith(
        ("all-reduce", "all-gather"))


def test_kernel_seconds_by_name():
    from perfbench.layer_metrics import attn_roofline_pct

    call = ('%%jvp__.%d = bf16[96,1024,64]{2,1,0} custom-call(bf16[96,1024,64]'
            '{2,1,0} %%q), custom_call_target="tpu_custom_call"')
    xent = ('%SoftMaxCrossEntropy.1 = f32[8192,1]{1,0} custom-call(f32[8192,'
            '50257]{1,0} %x), custom_call_target="tpu_custom_call"')
    tr = Trace(devices={0: [(call % 3, 0, 10), (xent, 10, 20),
                            (call % 7, 20, 50)]})
    rx = attn_roofline_pct.kernels(96, 1024, 64)
    assert xplane.kernel_seconds(tr, rx, 0, 50) == pytest.approx(40e-9)
    assert xplane.kernel_seconds(tr, rx, 0, 30) == pytest.approx(20e-9)
    assert xplane.kernel_seconds(tr, r"nothing", 0, 50) is None
    assert xplane.short_name(call % 3) == "jvp__.3 bf16[96,1024,64]"
    assert xplane.short_name("%f.1 = (f32[8]{0}, f32[4]{0}) fusion(f32[8]{0} "
                             "%p), kind=kLoop") == "f.1 f32[8]"


def test_idle_gaps_take_the_annotation_over_their_midpoint():
    gaps = xplane.idle_gaps(_two_chips(), 0, 100, 5, "host-loop")
    assert gaps == [["loss read", pytest.approx(20e-9)]]
    tr = _two_chips()
    tr.host = [("bench:window", 0, 100)]
    assert xplane.idle_gaps(tr, 0, 100, 5, "host-loop")[0][0] == "host-loop"


def test_recorded_training_trace_reduces():
    """12 ms of a gpt2-train-seq1024 run on the v5e (PR 22)."""
    from perfbench.layer_metrics import attn_roofline_pct

    tr = xplane.load(os.path.join(FIXTURES, "v5e_gpt2_train.xplane.pb"))
    assert sorted(tr.devices) == [0] and len(tr.devices[0]) > 500
    assert tr.asyncs[0] and all("-start" in n or "-done" in n
                                for n, _, _ in tr.asyncs[0][:50])
    w0, w1 = xplane.window(tr)           # no bench:window in the cut
    assert 11e6 < w1 - w0 < 13e6
    busy = xplane.busy_by_chip(tr, w0, w1)[0]
    assert 0.9 * (w1 - w0) / 1e9 < busy <= (w1 - w0) / 1e9
    assert 0 <= xplane.idle_pct(tr, w0, w1) < 10
    # the Pallas calls have no name of their own: found by their shapes
    attn = xplane.kernel_seconds(
        tr, attn_roofline_pct.kernels(96, 1024, 64), w0, w1)
    assert 1e-3 < attn < busy
    assert xplane.kernel_seconds(
        tr, attn_roofline_pct.kernels(96, 512, 64), w0, w1) is None
    top = xplane.top_ops(tr, w0, w1, 10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert all(len(name) < 80 and " = " not in name for name, _ in top)
    assert sum(t for _, t in xplane.top_ops(tr, w0, w1, 10 ** 6)) \
        == pytest.approx(busy, rel=0.02)
    assert xplane.collective_exposed_pct(tr, w0, w1) is None
    assert {n for n, _, _ in tr.host} == {"bench:model(x, y)",
                                          "bench:loss read"}


needs_fixture = pytest.mark.skipif(
    not os.path.isfile(FIXTURE), reason="no four-chip trace in the checkout")


@needs_fixture
def test_recorded_four_chip_trace_has_collectives():
    tr = xplane.load(FIXTURE)
    assert sorted(tr.devices) == [0, 1, 2, 3]
    assert all(tr.devices[c] for c in tr.devices)
    w0, w1 = xplane.window(tr)
    assert any(name == "bench:window" for name, _, _ in tr.host)
    busy = xplane.busy_by_chip(tr, w0, w1)
    assert all(0 < b < (w1 - w0) / 1e9 for b in busy.values())
    assert 0 < xplane.idle_pct(tr, w0, w1) < 100
    # jax names its all-reduce "psum...": collectives go by opcode
    psum = [o for o in tr.devices[1] if xplane.is_collective(o[0])]
    assert psum and all(o[0].startswith("%psum") for o in psum)
    assert xplane.opcode(psum[0][0]) == "all-reduce"
    # three all-reduces of about 40 us a chip, nothing beside them
    exposed = xplane.collective_exposed_pct(tr, w0, w1)
    assert exposed is not None and 0 < exposed < 1
    coll = sum(o[2] - o[1] for o in psum)
    assert exposed == pytest.approx(100 * coll / (w1 - w0), rel=0.2)
