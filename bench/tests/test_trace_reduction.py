"""The reduction from trace to numbers, on small synthetic traces."""

import pytest

from harness import roofline, scheme
from harness import trace as trc
from harness.report import Context

E = trc.Event
US = 1000.0   # ns


def _trace(ops, modules=(), host=None, window=(0.0, 100 * US), planes=1):
    devices = {f"/device:TPU:{k}": {trc.OPS_LINE: list(ops),
                                   trc.MODULES_LINE: list(modules)}
               for k in range(planes)}
    return trc.Trace(devices=devices, host=host or {}, window=window)


def test_merge_unions_overlapping_and_drops_empty():
    assert trc.merge([(5, 8), (0, 2), (1, 3), (8, 9), (4, 4)]) == \
        [(0, 3), (5, 9)]


def test_busy_is_the_union_clipped_to_the_window():
    ops = [E("a", 0, 10 * US), E("b", 5 * US, 10 * US),     # overlap: 15 us
           E("c", 40 * US, 10 * US),                        # 10 us
           E("d", 95 * US, 20 * US)]                        # 5 us inside
    t = _trace(ops)
    assert trc.busy_ns(t) == pytest.approx(30 * US)
    assert trc.idle_share(t) == pytest.approx(70.0)


def test_busy_averages_over_device_planes():
    t = _trace([E("a", 0, 50 * US)], planes=2)
    t.devices["/device:TPU:1"][trc.OPS_LINE] = [E("a", 0, 10 * US)]
    assert trc.busy_ns(t) == pytest.approx(30 * US)


def test_idle_share_is_none_without_device_ops():
    assert trc.idle_share(_trace([])) is None
    assert trc.idle_share(trc.Trace({}, {}, (0.0, 1.0))) is None


def test_select_by_name_and_inside_program_runs():
    runs = [E("jit_ingest(abc)", 0, 20 * US), E("jit_other", 50 * US, 20 * US)]
    ops = [E("scatter.1", 2 * US, 5 * US), E("fusion.3", 8 * US, 5 * US),
           E("scatter.2", 55 * US, 5 * US)]
    t = _trace(ops, runs)
    ingest = trc.executions(t, r"^jit_ingest")
    assert [r.name for r in ingest] == ["jit_ingest(abc)"]
    inside = trc.inside(t.ops(), ingest)
    assert [e.name for e in trc.select(inside, "scatter")] == ["scatter.1"]


def test_executions_count_runs_whose_midpoint_is_in_the_window():
    runs = [E("jit_ingest", -30 * US, 20 * US),     # before: out
            E("jit_ingest", -5 * US, 20 * US),      # midpoint 5 us: in
            E("jit_ingest", 90 * US, 30 * US)]      # midpoint 105 us: out
    t = _trace([], runs)
    assert len(trc.executions(t, "ingest")) == 1


def test_top_ops_name_ops_by_program_and_rank_by_time():
    runs = [E("jit_ingest(x)", 0, 50 * US)]
    ops = [E("scatter.1", 0, 30 * US), E("fusion", 30 * US, 10 * US),
           E("copy", 60 * US, 5 * US)]
    top = trc.top_ops(_trace(ops, runs), k=2)
    assert [n for n, _ in top] == ["jit_ingest/scatter.1", "jit_ingest/fusion"]
    assert top[0][1] == pytest.approx(30e-6)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [E("a", 0, 10 * US), E("b", 70 * US, 30 * US)]
    host = {"python": [E(trc.WINDOW_SPAN, 0, 100 * US),
                       E("PjitFunction(ingest)", 15 * US, 50 * US),
                       E("submit", 30 * US, 20 * US)]}
    gaps = trc.idle_gaps(_trace(ops, host=host), k=1)
    assert gaps == [["python: submit", pytest.approx(60e-6)]]


def test_roofline_share_takes_the_larger_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    # 1 MB at 1 GB/s = 1 ms least; measured 4 ms -> 25 %
    assert roofline.roofline_share(4e-3, peaks, nbytes=1e6) == pytest.approx(25.0)
    # flops bound is larger: 2e9 flops at 1e12 = 2 ms -> 50 %
    assert roofline.roofline_share(4e-3, peaks, nbytes=1e6, flops=2e9) == \
        pytest.approx(50.0)
    assert roofline.roofline_share(0.0, peaks, nbytes=1e6) is None


def test_unknown_device_kind_is_an_error():
    table = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}
    assert roofline.peak(table, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(roofline.UnknownDevice):
        roofline.peak(table, "TPU v9 imaginary")


def test_least_bytes_from_level_vectors():
    grids = scheme.combination_grids(3, 9)
    assert len(grids) == 109
    # 73,915 component points read + 511^3 surplus written, float32
    assert scheme.least_ingest_bytes(grids) == (73915 + 511 ** 3) * 4
    assert scheme.hierarchize_bytes([(2, 1), (1, 2)]) == 2 * (3 + 3) * 4


def test_metric_readers_on_a_synthetic_trace(tmp_path):
    from conftest import copy_layout
    from harness.layout import Layout
    lay = Layout(copy_layout(tmp_path))
    runs = [E("jit_ingest(a)", 0, 40 * US), E("jit_ingest(a)", 50 * US, 40 * US)]
    scatter = ("%fusion.30 = f32[16]{0:T(1024)} fusion(f32[16]{0:T(1024)} "
               "%fusion.29, s32[4]{0} %r, f32[4]{0} %u), kind=kCustom")
    sort = "%sort.2 = (s32[4]{0}, f32[4]{0}) sort(s32[4]{0} %r, f32[4]{0} %u)"
    fill = "%broadcast.1 = f32[16]{0} broadcast(f32[] %constant.3)"
    kernel = "%tail.1 = f32[2,3,7]{2,1,0} custom-call(f32[2,3,7]{2,1,0} %x)"
    ops = [E(scatter, 0, 8 * US), E(sort, 8 * US, 2 * US),
           E(fill, 12 * US, 5 * US), E(scatter, 50 * US, 10 * US),
           E(kernel, 20 * US, 10 * US), E(kernel, 70 * US, 10 * US)]
    ctx = Context(cell="x", config={}, traffic={}, trace=_trace(ops, runs),
                  counters={"eval.queries": 12, "eval.batches": 4},
                  counters_trace={},
                  sizes={"fine_points": 15, "least_ingest_bytes": 8200,
                         "hier_kernel_bytes": 4100},
                  peaks={"hbm_bytes_per_s": 819e6, "bf16_flops_per_s": 1e12})
    read = lambda name: lay.metric_reader(name).read(ctx)
    assert read("scatter_ms_per_ingest") == pytest.approx(0.010)
    # 8200 B at 819 MB/s = ~10.01 us of 40 us per run
    assert read("ingest_roofline") == pytest.approx(100 * 8200 / 819e6 / 40e-6)
    # 2 runs x 4100 B over 20 us of kernel time
    assert read("hier_kernel_roofline") == pytest.approx(
        100 * 2 * 4100 / 819e6 / 20e-6)
    assert read("queries_per_eval_batch") == pytest.approx(3.0)
    # busy: 0-10, 12-17, 20-30, 50-60, 70-80 us of 100 us
    assert read("device_idle_share.refresh") == pytest.approx(55.0)
    # no trace, nothing to read: the reader returns nothing, never 0
    ctx.trace = None
    assert read("ingest_roofline") is None
    assert read("eval_ms_per_query") is None


def test_a_listed_metric_that_reads_nothing_stops_the_run(tmp_path):
    from conftest import copy_layout
    from harness import report
    from harness.layout import Layout
    lay = Layout(copy_layout(tmp_path))
    # the ingest program renamed: device ops, but no run to read
    t = _trace([E("fusion.1", 0, 10 * US)], [E("jit_refresh(a)", 0, 20 * US)])
    ctx = Context(cell="x", config={}, traffic={}, trace=t, counters={},
                  counters_trace={},
                  sizes={"fine_points": 15, "least_ingest_bytes": 8200,
                         "hier_kernel_bytes": 4100},
                  peaks={"hbm_bytes_per_s": 819e6, "bf16_flops_per_s": 1e12})
    with pytest.raises(report.MetricMissing,
                       match=r"scatter_ms_per_ingest.*jit_refresh"):
        report.per_layer(lay, "fig6_2d.refresh", ctx)
    # a trace with no device operations (off the chip) has nothing to
    # read for trace metrics; they are left out
    ctx.trace = _trace([], [])
    assert report.per_layer(lay, "fig6_2d.refresh", ctx) == {}
    # a counter the cell lists is always there to read
    with pytest.raises(report.MetricMissing, match="queries_per_eval_batch"):
        report.per_layer(lay, "fig6_2d.query", ctx)


def test_program_samples_keep_one_whole_run_of_each_program():
    runs = [E("jit_ingest(a)", -5 * US, 10 * US),     # starts before: skipped
            E("jit_ingest(a)", 20 * US, 10 * US),
            E("jit_ingest(a)", 40 * US, 10 * US),     # second run: skipped
            E("jit_eval(b)", 60 * US, 10 * US)]
    ops = [E("scatter.1", 22 * US, 3 * US), E("sort.1", 25 * US, 2 * US),
           E("scatter.1", 42 * US, 3 * US), E("dot.1", 61 * US, 5 * US)]
    samples = trc.program_samples(_trace(ops, runs))
    assert [s["module"] for s in samples] == ["jit_ingest(a)", "jit_eval(b)"]
    assert samples[0]["ops"] == [["scatter.1", 2 * US, 3 * US],
                                 ["sort.1", 5 * US, 2 * US]]
    assert samples[1]["dur_ns"] == 10 * US
