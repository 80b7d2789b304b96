"""The trace reduction and the per-layer readers, on hand-made traces with
known answers and on a trace recorded on a TPU v5 lite."""
import importlib
import os

import pytest

from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "hvdc_horizontal_trace.json.gz")


def _op(name, s, e):
    return T.Op(name, float(s), float(e))


def _hand_trace():
    """Two chips, window [1000, 11000] ns: chip 0 busy 6000 ns with an
    overlap and an op sticking out of the window; chip 1 busy 2000 ns."""
    dev0 = [_op("fusion.1", 0, 2000),                 # 1000 ns inside
            _op("%custom-call.2 = f32[8] custom-call()", 3000, 4000),
            _op("fusion.2", 3500, 5000),               # overlaps: union 2000
            _op("%while.1 = (f32[1,18]) while()", 8000, 11000),    # 3000
            _op("fusion.3", 10500, 12000)]             # inside: covered
    dev1 = [_op("%custom-call.2 = f32[8] custom-call()", 2000, 3000),
            _op("%fusion.4 = f32[1,18] fusion()", 5000, 6000)]
    spans = [_op("chipbench.window", 1000, 11000),
             _op("chipbench.epoch_done", 6000, 6500)]
    return T.Trace(devices=[dev0, dev1], spans=spans, window=(1000.0, 11000.0))


def test_union_and_gaps():
    tr = _hand_trace()
    assert tr.busy_s() == pytest.approx([6000e-9, 2000e-9])
    assert T.idle_gaps(tr.devices[0], tr.window) == [(2000.0, 3000.0),
                                                      (5000.0, 8000.0)]
    assert tr.window_s == pytest.approx(10000e-9)


def test_breakdown_names_gaps_by_innermost_span():
    b = T.breakdown(_hand_trace())
    assert b["idle_gaps"][0] == ["chipbench.epoch_done", pytest.approx(3e-6)]
    assert b["idle_gaps"][1] == ["chipbench.window", pytest.approx(1e-6)]
    # self time, averaged over the 2 chips: while.1 holds fusion.3 for
    # 500 ns of its 3000 on chip 0
    assert b["device_ops"][0] == ["%while.1", pytest.approx(2500e-9 / 2)]


def test_save_load_round_trip(tmp_path):
    tr = _hand_trace()
    tr.save(str(tmp_path / "t.json.gz"))
    back = T.Trace.load(str(tmp_path / "t.json.gz"))
    assert back.devices == tr.devices and back.window == tr.window


def _read(name, trace, **info):
    base = dict(epochs=2, generations_per_epoch=5, islands_per_chip=2,
                pop_per_island=4, solves_per_eval=1)
    base.update(info)
    return importlib.import_module(f"chipbench.metrics.{name}").read(trace,
                                                                     base)


def test_readers_on_hand_trace():
    tr = _hand_trace()
    # busy 6000 and 2000 ns of a 10000 ns window: 60% idle on average
    assert _read("device_idle_share", tr) == pytest.approx(60.0)
    assert _read("epoch_device_ms", tr) == pytest.approx(4000e-6 / 2)
    # 4000 ns busy per chip over 2 epochs of 5 generations of 8 solves
    assert _read("newton_solve_ms", tr) == pytest.approx(4000e-6 / 80)
    assert _read("newton_lu_ms", tr) is None


def test_lu_reader_takes_the_calls_not_their_readers():
    lu = ('%custom-call.7 = (f32[5430,128], s32[128]) custom-call(f32[5430,'
          '128] %p), custom_call_target="LuDecompositionBlock"')
    inv = ('%custom-call.8 = f32[43,128,128] custom-call(f32[43,128,128] %q),'
           ' custom_call_target="InvertDiagBlocksUpperTriangular"')
    tri = "%triangular-solve.2 = f32[5430,1] triangular-solve(f32[5430,5430] %a)"
    reader = ("%fusion.9 = f32[5430] fusion(%custom-call.7, %custom-call.8, "
              "%triangular-solve.2)")
    tr = T.Trace(devices=[[_op(lu, 0, 3000), _op(inv, 3000, 3500),
                           _op(tri, 3500, 4000), _op(reader, 4000, 9000)]],
                 spans=[], window=(0.0, 10000.0))
    # 4000 ns over 2 solves
    assert _read("newton_lu_ms", tr, epochs=1, generations_per_epoch=1,
                 islands_per_chip=1, pop_per_island=2) == pytest.approx(2e-3)


def test_readers_find_nothing_on_an_empty_trace():
    tr = T.Trace(devices=[], spans=[], window=(0.0, 1.0))
    for name in ("device_idle_share", "epoch_device_ms", "newton_solve_ms",
                 "newton_lu_ms"):
        assert _read(name, tr) is None, name


def test_readers_on_recorded_trace():
    """A 4.98 ms slice of a traced ``hvdc_horizontal`` window, recorded on
    a TPU v5 lite, inside one Newton solve: three panels of the LU
    factorization and the diagonal-block inversions between them."""
    tr = T.Trace.load(RECORDED)
    one = dict(epochs=1, generations_per_epoch=1, islands_per_chip=1,
               pop_per_island=1)
    # the solve's while loops span the slice: no idle time
    assert _read("device_idle_share", tr, **one) == pytest.approx(0.0)
    assert _read("epoch_device_ms", tr, **one) == pytest.approx(4.975478)
    assert _read("newton_solve_ms", tr, **one) == pytest.approx(4.975478)
    # LuDecompositionBlock 485666 + 474381 + 463798 ns (the first from
    # the slice's start), InvertDiagBlocks 3 x about 10157 ns
    assert _read("newton_lu_ms", tr, **one) == pytest.approx(1.454315)
    ops = dict(T.breakdown(tr)["device_ops"])
    assert max(ops, key=ops.get) == "%fusion.2305"
    assert ops["%custom-call.1785"] == pytest.approx(485666e-9)
