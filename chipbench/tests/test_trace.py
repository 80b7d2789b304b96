"""The trace reduction and the per-layer readers, on hand-made traces with
known answers and on a trace recorded on a TPU v5 lite."""
import importlib
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "hvdc_horizontal_trace.json.gz")


def _op(name, s, e, scope=""):
    return T.Op(name, float(s), float(e), scope)


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


SCOPE_READERS = ("selection_ms", "variation_ms", "survivor_ms",
                 "migration_ms", "framework_overhead_share")


def test_readers_find_nothing_on_an_empty_trace():
    tr = T.Trace(devices=[], spans=[], window=(0.0, 1.0))
    for name in ("device_idle_share", "epoch_device_ms", "newton_solve_ms",
                 "newton_lu_ms") + SCOPE_READERS:
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
    # recorded before ops carried scopes: the scope readers find nothing
    for name in SCOPE_READERS:
        assert _read(name, tr, **one) is None, name


def _scoped_trace():
    """One chip, window [0, 10000] ns, every op under a scope or not:
    selection 1000 ns, variation 500, the fitness's while loop 4000 of
    which its LU body op 2500, survivor 1000, migration 300, an unscoped
    copy 200, then 3000 idle."""
    ops = [_op("%a = sel", 0, 1000, "chambga.selection"),
           _op("%b = var", 1000, 1500, "chambga.variation"),
           _op("%w = while", 1500, 5500, "chambga.fitness"),
           _op("%lu = call", 2000, 4500,
               "chambga.fitness/chambga.newton.lu"),
           _op("%c = surv", 5500, 6500, "chambga.survivor"),
           _op("%d = mig", 6500, 6800, "chambga.migration"),
           _op("%copy = copy", 6800, 7000, "")]
    return T.Trace(devices=[ops], spans=[], window=(0.0, 10000.0))


def test_scope_readers_on_hand_trace():
    tr = _scoped_trace()
    # every op's self time lands in one scope: together, the busy time
    assert sum(tr.scope_s()[0].values()) == pytest.approx(tr.busy_s()[0])
    assert tr.scope_s()[0]["chambga.fitness"] == pytest.approx(1500e-9)
    one = dict(epochs=1, generations_per_epoch=2)
    assert _read("selection_ms", tr, **one) == pytest.approx(1000e-6 / 2)
    assert _read("variation_ms", tr, **one) == pytest.approx(500e-6 / 2)
    assert _read("survivor_ms", tr, **one) == pytest.approx(1000e-6 / 2)
    assert _read("migration_ms", tr, **one) == pytest.approx(300e-6)
    # outside the fitness: 1000 + 500 + 1000 + 300 + 200 of 7000 busy
    assert _read("framework_overhead_share", tr, **one) == pytest.approx(
        100 * 3000 / 7000)


def test_scope_readers_skip_a_scope_with_no_ops():
    tr = _scoped_trace()
    tr.devices[0] = [o for o in tr.devices[0]
                     if o.scope != "chambga.migration"]
    assert _read("migration_ms", tr) is None
    assert _read("selection_ms", tr) is not None


def _profile(ops, modules, window=(0, 10000)):
    """A profile as ``jax.profiler.ProfileData`` gives it: one TPU plane
    with its ops and modules lines, one host plane with the window."""
    def events(spec):
        return [NS(name=n, start_ns=float(s), end_ns=float(e))
                for n, s, e in spec]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=events(modules)),
        NS(name="XLA Ops", events=events(ops))])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=events([("chipbench.window", *window)]))])
    return NS(planes=[device, host])


def test_scopes_go_to_the_programs_ops_alone():
    scopes = ("jit_epoch_step", {"%fusion.1": "chambga.selection",
                                 "%while.2": "chambga.fitness"})
    pd = _profile(
        ops=[("%fusion.1 = f32[8] fusion(%p)", 100, 200),
             ("%while.2 = (f32[8]) while(%t)", 200, 900),
             ("%fusion.3 = f32[8] fusion(%q)", 900, 1000),
             ("%fusion.1 = f32[2] fusion(%x)", 2100, 2200),   # jit_copy's
             ("%fusion.1 = f32[8] fusion(%p)", 3000, 3100),   # outside
             ("%fusion.1 = f32[8] fusion(%p)", 4100, 4200)],
        modules=[("jit_epoch_step(123)", 100, 1000),
                 ("jit_copy(9)", 2000, 2500),
                 ("jit_epoch_step(123)", 4000, 5000)])
    tr = T.from_profile(pd, scopes)
    assert [o.scope for o in tr.devices[0]] == [
        "chambga.selection", "chambga.fitness", "", "", "",
        "chambga.selection"]
    # without a map no op has a scope
    assert {o.scope for o in T.from_profile(pd).devices[0]} == {""}


def test_scope_map_reads_innermost_last():
    text = """HloModule jit_epoch_step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %lu.1 = f32[8] custom-call(f32[8] %p), metadata={op_name="jit(epoch_step)/while/body/chambga.fitness/jit(_solve)/chambga.newton.lu/lu" source_file="x.py"}
  ROOT %fusion.4 = f32[8] fusion(f32[8] %lu.1), kind=kLoop, metadata={op_name="jit(epoch_step)/while/body/vmap(chambga.variation)/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %copy.2 = f32[8] copy(f32[8] %a)
  %sort.3 = f32[8] sort(f32[8] %copy.2), metadata={op_name="jit(epoch_step)/while/sort"}
  ROOT %while.5 = f32[8] while(f32[8] %sort.3), body=%body
}
"""
    module, scopes = T.scope_map(text)
    assert module == "jit_epoch_step"
    assert scopes == {"%lu.1": "chambga.fitness/chambga.newton.lu",
                      "%fusion.4": "chambga.variation"}


@pytest.mark.parametrize("make", [_hand_trace, _scoped_trace])
def test_save_load_round_trip(tmp_path, make):
    tr = make()
    tr.save(str(tmp_path / "t.json.gz"))
    back = T.Trace.load(str(tmp_path / "t.json.gz"))
    assert back.devices == tr.devices and back.window == tr.window


def test_every_scope_of_the_epoch_step_names_an_op():
    """The 60-bus HVDC epoch step compiled on the CPU: each ``chambga.*``
    name in its HLO is the innermost scope of some op of the map, and the
    map holds the program's GA and Newton scopes."""
    import jax
    from chipbench.configs import hvdc_german
    from chipbench.tests.test_harness_cpu import MIX, tiny_hvdc
    from repro.core.engine import GAEngine

    dep = hvdc_german.build(tiny_hvdc(), MIX, seed=1, chips=1)
    eng = GAEngine(dep.cfg, dep.fitness, cost_fn=dep.cost_fn, ctx=dep.ctx)
    text = eng._epoch_step.lower(eng.init(dep.ga_seed)).compile().as_text()
    module, scopes = T.scope_map(text)
    assert module == "jit_epoch_step"
    innermost = {v.rsplit("/", 1)[-1] for v in scopes.values()}
    assert set(T.SCOPE.findall(text)) <= innermost
    assert innermost >= {"chambga.selection", "chambga.variation",
                         "chambga.fitness", "chambga.survivor",
                         "chambga.migration", "chambga.newton.mismatch",
                         "chambga.newton.jacobian", "chambga.newton.lu"}
    assert all(v.startswith("chambga.fitness/") for v in scopes.values()
               if "newton" in v)
