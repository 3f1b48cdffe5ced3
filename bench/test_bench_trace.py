"""Trace reduction on hand-built traces (no profiler, no chip)."""

import pytest

from bench import trace
from bench.trace import Op, Trace

KERNELS = r"^(project_packed|reconstruct_apply_packed)(\.\d+)?$"


def _dev():
    # one 10 s window: a projection kernel, forward ops, a collective
    # that half overlaps a fusion, and a 2 s gap while the host made data
    return [
        Op("fusion.1", 0.0, 1.0),
        Op("project_packed.1", 1.0, 3.0),
        Op("all-gather-start.2", 3.0, 3.5),
        Op("fusion.2", 3.25, 4.0),
        Op("reconstruct_apply_packed.1", 4.0, 7.0),
        Op("fusion.3", 9.0, 10.0),
    ]


def _trace():
    spans = [Op("bench.window", 0.0, 10.0), Op("bench.data", 6.8, 9.2),
             Op("bench.wait", 0.0, 6.8)]
    return Trace({0: _dev()}, spans, (0.0, 10.0))


def test_union_and_overlap():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert trace.length([(0, 2), (3, 5)]) == 4
    assert trace.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2


def test_busy_and_idle():
    ops = _dev()
    assert trace.busy_s(ops) == pytest.approx(8.0)
    gaps = trace.idle_gaps(ops, _trace().spans, (0.0, 10.0))
    assert gaps == [["bench.data", pytest.approx(2.0)]]


def test_kernel_time_by_name():
    ops = _dev()
    assert trace.kernel_s(ops, r"^project_packed") == pytest.approx(2.0)
    assert trace.kernel_s(ops, r"^reconstruct_apply") == pytest.approx(3.0)
    assert trace.kernel_s(ops, r"^no_such_kernel") == 0.0


def test_collectives_and_exposure():
    ops = _dev()
    assert trace.busy_s(trace.collectives(ops)) == pytest.approx(0.5)
    # 3.0-3.25 has nothing else running; 3.25-3.5 overlaps fusion.2
    assert trace.exposed_s(ops) == pytest.approx(0.25)


def test_busy_outside_kernels_and_collectives():
    # fusions: 1.0 + (3.5-4.0) + 1.0; fusion.2's first 0.25 s overlaps
    # the collective and counts as the collective's
    assert trace.busy_outside_s(_dev(), KERNELS) == pytest.approx(2.5)


def test_clip_to_window():
    t = Trace({0: _dev()}, [Op("bench.window", 2.0, 5.0)], (2.0, 5.0))
    c = t.clipped()
    assert trace.busy_s(c.devices[0]) == pytest.approx(3.0)
    assert min(o.start for o in c.devices[0]) == 2.0


def test_top_ops_groups_numeric_suffixes():
    top = trace.top_ops(_dev())
    assert top[0] == ["reconstruct_apply_packed", pytest.approx(3.0)]
    assert ["fusion", pytest.approx(2.75)] in top


def test_op_name_of_an_hlo_event():
    ev = ("%project_packed.1 = (f32[2384,1]{1,0}, f32[2384,1]{1,0}) "
          'custom-call(s32[1]{0} %constant.383), custom_call_target='
          '"tpu_custom_call"')
    assert trace.op_name(ev) == "project_packed.1"
    assert trace.op_name("fusion.2") == "fusion.2"
