"""Units of the benchmark: trace reduction, byte counts and roofline,
percentiles from due times, the open-loop schedule, discovery by name,
and the references against the DFG interpreter."""
import json
import math

import numpy as np
import pytest

from conftest import ROOT


# -- trace reduction ---------------------------------------------------------

def _trace(ops, host=(), window=(0.0, 100.0)):
    from chipbench import xtrace
    return xtrace.Trace(window, [xtrace.DeviceTrace("/device:TPU:0",
                                                    list(ops))], list(host))


def test_union_and_gaps_clip_to_the_window():
    from chipbench import xtrace
    busy = xtrace.union([(10, 20), (15, 30), (40, 50), (90, 120), (-5, 2)],
                        (0, 100))
    assert busy == [(0, 2), (10, 30), (40, 50), (90, 100)]
    assert xtrace.gaps(busy, (0, 100)) == [(2, 10), (30, 40), (50, 90)]


def test_idle_share_and_kernel_time_by_name():
    k = "%_traced.1 = s32[8192,128]{1,0} custom-call(s32[1,1] %a)"
    c = "%copy = s32[128,8192]{1,0} copy(s32[128,8192] %b)"
    t = _trace([(c, 0, 10), (k, 10, 40), (k, 45, 75), (c, 70, 80)])
    assert t.busy_s() == pytest.approx(75e-9)
    assert t.idle_pct() == pytest.approx(25.0)
    evs = t.kernel_events()
    assert [(e.start, e.end, e.words, e.lanes) for e in evs] == \
        [(10, 40, 8192, 128), (45, 75, 8192, 128)]
    assert t.top_ops(1) == [["custom-call %_traced.1", pytest.approx(60e-9)]]


def test_idle_gaps_go_to_the_covering_host_span():
    k = "%k = s32[8,8]{1,0} custom-call(%a)"
    t = _trace([(k, 0, 10), (k, 60, 100)],
               host=[("submit", 5, 20), ("generate", 20, 58),
                     ("drain", 59, 61)])
    got = dict((n, s) for n, s in t.idle_by_host_span())
    assert got == {"generate": pytest.approx(50e-9)}


def test_no_device_plane_reads_as_nothing():
    from chipbench import xtrace
    t = xtrace.Trace((0.0, 1.0), [], [])
    assert t.idle_pct() is None and t.kernel_events() == []


def test_recorded_tpu_trace_reduces():
    """A trace of the harness on one TPU v5e: a short bulk window."""
    from chipbench import xtrace
    path = xtrace.find_xplane(str(ROOT / "tests" / "chipbench" / "data"))
    t = xtrace.load(path)
    assert t.window is not None and t.window_s > 0
    evs = t.kernel_events()
    assert evs and all((e.words, e.lanes) == (8192, 128) for e in evs)
    assert 0.0 <= t.idle_pct() < 100.0
    assert t.busy_s() <= t.window_s
    names = [n for n, _ in t.top_ops()]
    assert names[0].startswith("custom-call")


# -- byte counts, peaks, roofline ------------------------------------------

def test_block_bytes_and_hbm_bound():
    from chipbench import shapes
    from chipbench.peaks import peaks_for
    assert shapes.block_bytes(8192, 128) == 2 * 8192 * 128 * 4
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    assert shapes.hbm_bound_s(8192, 128, bw) == pytest.approx(
        8388608 / 819e9)


def test_unknown_device_kind_is_an_error():
    from chipbench.peaks import peaks_for
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    assert peaks_for("TPU v5 lite")["int32_vpu_ops_per_s"] is None


def test_roofline_reader_against_the_table():
    from chipbench import harness
    k = "%k = s32[8192,128]{1,0} custom-call(%a)"
    t = _trace([(k, 0, 2_000_000), (k, 2_000_000, 4_000_000)],
               window=(0.0, 4e6))
    ctx = harness.Context(cell=None, driver=None, trace=t, spans=[],
                          before={}, after={},
                          peaks={"hbm_bytes_per_s": 819e9})
    share = harness.load_reader("cgra_exec_roofline")(ctx)
    assert share == pytest.approx(100 * (8388608 / 819e9) / 2e-3)
    per = harness.load_reader("kernel_us_per_block.bulk")(ctx)
    assert per == pytest.approx(2000.0)
    ctx.peaks = {}
    assert harness.load_reader("cgra_exec_roofline")(ctx) is None


# -- percentiles from due times ----------------------------------------------

def test_nearest_rank_percentile_over_all_values():
    from chipbench.stats import percentile
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 95) == 95
    assert percentile([3.0], 95) == 3.0
    assert percentile([1, 2, math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        percentile([], 50)


# -- the open-loop schedule ---------------------------------------------------

def test_schedule_is_fixed_by_the_seed_and_its_count_by_the_rate():
    from chipbench import generator
    mix = {"rate_per_s": 250.0}
    a = generator.arrivals(mix, 2**31 + 11, 4.0)
    b = generator.arrivals(mix, 2**31 + 11, 4.0)
    c = generator.arrivals(mix, 5, 4.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 1000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 4.0


def test_inputs_follow_the_configuration():
    from chipbench import generator
    cfg = json.loads((ROOT / "chipbench/configs/pace8x8-fft.json")
                     .read_text())
    x = generator.make_inputs(cfg, 2**33 + 1, 16)
    y = generator.make_inputs(cfg, 2**33 + 1, 16)
    assert all(np.array_equal(x[k], y[k]) for k in x)
    assert x["ar"].dtype == np.int32 and x["ar"].shape == (16, 16)
    assert x["ar"].min() >= -32768 and x["ar"].max() < 32768
    assert x["wr"][0, 0] == 256 and x["wi"][0, 8] == -256


# -- discovery by name ----------------------------------------------------------

def test_every_cell_metric_and_mix_is_found_by_name():
    from chipbench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["loop"] in harness.DRIVERS
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
        assert (ROOT / "chipbench" / "references" /
                f"{cell.config['reference']}.py").is_file()
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.load_cell("no-such.cell")


def test_a_mix_with_a_parameter_no_loop_reads_is_refused(monkeypatch,
                                                        tmp_path):
    from chipbench import generator
    monkeypatch.setattr(generator, "TRAFFIC_DIR", tmp_path)
    (tmp_path / "x.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 5, "pool": 8, "buckets": [1],
         "tenants": 3}))
    with pytest.raises(ValueError, match="tenants"):
        generator.load_mix("x")
    (tmp_path / "y.json").write_text(json.dumps({"loop": "burst"}))
    with pytest.raises(ValueError, match="loop"):
        generator.load_mix("y")


def test_readers_read_nothing_from_an_empty_run():
    from chipbench import harness
    ctx = harness.Context(cell=None, driver=None, trace=None, spans=[],
                          before={}, after={}, peaks={})
    names = [p.stem for p in (ROOT / "chipbench" / "metrics").glob("*.py")]
    assert "router_skew_pct.serve" in names
    for name in names:
        assert harness.load_reader(name)(ctx) is None, name


# -- the references -------------------------------------------------------------

@pytest.mark.parametrize("config", ["hycube4x4-gemm", "pace8x8-fft"])
def test_reference_matches_the_dfg_interpreter(config):
    from chipbench import generator, harness
    from repro.core.dfg import interpret
    from repro.core.kernel_lib import KERNELS
    cfg = json.loads((ROOT / f"chipbench/configs/{config}.json")
                     .read_text())
    x = generator.make_inputs(cfg, 123, 6)
    got = harness.load_reference(cfg["reference"]).run(x, cfg["n_iters"])
    dfg, _, _ = KERNELS[cfg["kernel"]]()
    for i in range(6):
        want = interpret(dfg, {k: v[i] for k, v in x.items()},
                         cfg["n_iters"])
        for k in cfg["outputs"]:
            assert np.array_equal(got[k][i], want[k]), (k, i)


@pytest.mark.parametrize("config", ["hycube4x4-gemm", "pace8x8-fft"])
def test_the_16_bit_control_differs_on_the_configured_inputs(config):
    from chipbench import generator, harness
    cfg = json.loads((ROOT / f"chipbench/configs/{config}.json")
                     .read_text())
    x = generator.make_inputs(cfg, 99, 64)
    ref = harness.load_reference(cfg["reference"])
    a, b = ref.run(x, cfg["n_iters"]), ref.run(x, cfg["n_iters"], bits=16)
    differ = np.zeros(64, bool)
    for k in cfg["outputs"]:
        differ |= (a[k] != b[k]).any(axis=1)
    assert differ.mean() > 0.25


def test_counter_readers_take_the_window_increments():
    from chipbench import harness

    def svc(completed, batches, calls, slots):
        return {"service": {
            "completed": completed, "batches": batches,
            "engine": {"per_engine": {"e0": {"bucket_calls": calls}}},
            "router": {"slots": [{"samples": s} for s in slots]}}}

    before = svc(100, 10, {1: 3, 32: 2}, [10, 10, 10, 10])
    after = svc(400, 30, {1: 3, 8: 10, 32: 12}, [110, 60, 85, 45])
    cell = harness.Cell("c", 4, {"lanes": 128}, {}, [], [])
    ctx = harness.Context(cell=cell, driver=None, trace=None, spans=[],
                          before=before, after=after, peaks={})
    assert harness.load_reader("mean_batch.serve")(ctx) == 15.0
    assert harness.load_reader("lane_fill_pct.serve")(ctx) == \
        pytest.approx(100 * 300 / (20 * 128))
    # slots served 100/50/75/35 in the window: max 100 over mean 65
    assert harness.load_reader("router_skew_pct.serve")(ctx) == \
        pytest.approx(100 * (100 / 65 - 1))


def test_service_wait_reads_queue_plus_coalesce_per_request():
    from chipbench import harness
    from repro.obs.trace import Span

    def sp(name, tid, dur):
        return Span(name, 0.0, dur, tid, name + tid)

    spans = [sp("request", "a", 0.010), sp("queue", "a", 0.001),
             sp("coalesce", "a", 0.002), sp("exec", "a", 0.005),
             sp("queue", "b", 0.003), sp("coalesce", "b", 0.004),
             sp("request", "c", 0.001)]
    ctx = harness.Context(cell=None, driver=None, trace=None, spans=spans,
                          before={}, after={}, peaks={})
    assert harness.load_reader("service_wait_ms.serve")(ctx) == \
        pytest.approx(5.0)
