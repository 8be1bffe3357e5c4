"""The trace reductions, on hand-made intervals and on a small trace
recorded on a v5e chip (`data/v5e_tiny.xplane.pb`: four calls of a
2048 x 2048 bf16 matmul program, each followed by a 10 ms sleep, between
the benchmark's host spans)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from chipbench import trace as tr  # noqa: E402

def hand_trace():
    w = (0.0, 10.0, "cb.window")
    d0 = tr.Device("/device:TPU:0", ops=[
        (1.0, 3.0, "fusion.1"), (2.0, 4.0, "all-gather-start"),
        (6.0, 7.0, "all-reduce.3"), (8.0, 9.0, "fusion.1")])
    d1 = tr.Device("/device:TPU:1", ops=[(0.0, 10.0, "fusion.2")])
    spans = [w, (4.5, 5.5, "cb.sync"), (4.0, 8.0, "cb.dispatch")]
    return tr.Trace([d0, d1], sorted(spans))


def test_busy_and_idle():
    t = hand_trace()
    # device 0 busy 1-4, 6-7, 8-9 = 5 s; device 1 all 10 s
    assert tr.busy_s(t) == pytest.approx(7.5)
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["cb.sync", pytest.approx(2.0)]       # 4-6, middle 5
    assert ["cb.dispatch", pytest.approx(1.0)] in gaps      # 7-8
    assert ["none", pytest.approx(1.0)] in gaps             # 0-1 and 9-10
    assert sum(g[1] for g in gaps) == pytest.approx(5.0)


def test_top_ops():
    t = hand_trace()
    top = dict((n, s) for n, s in tr.top_ops(t))
    assert top["fusion.2"] == pytest.approx(5.0)
    assert top["fusion.1"] == pytest.approx(1.5)
    assert top["all-gather-start"] == pytest.approx(1.0)
    assert list(top)[0] == "fusion.2"           # longest first


def test_window_clips_everything():
    t = hand_trace()
    t.spans = [s if s[2] != "cb.window" else (2.0, 8.5, "cb.window")
               for s in t.spans]
    # device 0 busy 2-4, 6-7, 8-8.5 = 3.5; device 1 6.5
    assert tr.busy_s(t) == pytest.approx(5.0)


def test_recorded_chip_trace():
    t = tr.load(str(DATA / "v5e_tiny.xplane.pb"))
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    lo, hi = t.window()
    names = [s[2] for s in t.spans]
    assert names.count("cb.dispatch") == 4 and names.count("cb.sleep") == 4
    # device ops and host spans share one clock: every op lies in the
    # window, after the first dispatch began
    ops = t.devices[0].ops
    assert min(o[0] for o in ops) >= lo and max(o[1] for o in ops) <= hi
    assert 0 < tr.busy_s(t) < hi - lo
    # the 10 ms sleeps leave the chip idle, and the gaps say so
    gaps = tr.idle_gaps(t)
    assert sum(1 for g in gaps if g[0] == "cb.sleep" and g[1] > 0.008) == 4
    assert tr.top_ops(t)[0][1] > 0
