"""The trace reduction, on a small trace recorded on a TPU v5e and on
hand-made intervals.

``data/tiny_tpu.xplane.pb`` holds three host spans ``engine.step`` and,
on ``/device:TPU:0``, three runs each of the decode-attention kernel's
program (``jit_decode_attention``) and of a small matmul program.
"""

import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")
WINDOW = (52_000_000, 56_000_000)


@pytest.fixture(scope="module")
def red():
    return tr.reduce(DATA, window=WINDOW)


def test_planes_lines_and_spans(red):
    assert [d.name for d in red.devices] == ["/device:TPU:0"]
    dev = red.devices[0]
    assert len(dev.ops) == 57
    assert sorted({n.split("(")[0] for n, _, _ in dev.modules}) == [
        "jit__lambda", "jit_decode_attention"]
    assert len(dev.modules) == 6
    assert len(red.spans["engine.step"]) == 3
    assert all(not n.startswith("%") for n, _, _ in dev.ops)


def test_kernel_time_by_name(red):
    # the three kernel events last 9993, 9995 and 9995 ns in the trace
    secs = tr.op_seconds(red, lambda n: "decode_attention" in n)
    assert secs == {"decode_attention.1": pytest.approx(29983e-9)}


def test_busy_is_the_union_of_leaf_ops(red):
    ops = sorted((s, e) for _, s, e in red.devices[0].leaves)
    merged = []
    for s, e in ops:                       # brute-force merge
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    want = sum(e - s for s, e in merged) / 1e9
    assert tr.busy_s(red) == pytest.approx(want)
    assert 0 < tr.busy_s(red) < red.window_s


def test_idle_inside_host_spans(red):
    busy = tr.busy(red.devices[0], *WINDOW)
    want = 0.0
    for s, e in red.spans["engine.step"]:
        s, e = max(s, WINDOW[0]), min(e, WINDOW[1])
        covered = sum(max(0, min(e, be) - max(s, bs)) for bs, be in busy)
        want += (e - s) - covered
    assert tr.idle_inside(red, "engine.step") == pytest.approx(want / 1e9)


def test_breakdown_lists(red):
    top = tr.device_ops_top(red, 3)
    assert top[0] == ["decode_attention.1", pytest.approx(29983e-9)]
    gaps = tr.idle_gaps_top(red, 3)
    assert len(gaps) == 3
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_leaves_skip_enclosing_ops():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 30, 40),
           ("copy.1", 100, 110), ("custom-call", 105, 105)]
    assert [n for n, _, _ in tr.leaf_ops(ops)] == [
        "fusion.1", "fusion.2", "copy.1", "custom-call"]


def test_exposed_collective():
    dev = tr.Device("/device:TPU:0", [
        ("collective-permute-done.1", 10, 20), ("fusion.1", 15, 30),
        ("all-reduce.2", 40, 50)], [])
    red = tr.Reduced(window=(0, 100), devices=[dev], spans={})
    assert tr.exposed_collective_s(red) == pytest.approx(15e-9)


def test_while_loop_hides_neither_idle_nor_collectives():
    # a loop of two steps, each a fusion then a halo exchange, with idle
    # gaps between the body's ops; the ``while`` op spans them all
    ops = [("while.3", 0, 100),
           ("fusion.1", 0, 30), ("collective-permute-done.1", 30, 40),
           ("fusion.1", 50, 80), ("collective-permute-done.1", 80, 90)]
    dev = tr.Device("/device:TPU:0", ops, [])
    red = tr.Reduced(window=(0, 100), devices=[dev], spans={})
    assert tr.busy(dev, 0, 100) == [(0, 40), (50, 90)]
    assert tr.busy_s(red) == pytest.approx(80e-9)
    assert tr.exposed_collective_s(red) == pytest.approx(20e-9)
    assert tr.op_seconds(red) == {
        "fusion.1": pytest.approx(60e-9),
        "collective-permute-done.1": pytest.approx(20e-9)}
