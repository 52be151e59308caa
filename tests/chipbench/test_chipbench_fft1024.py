"""The ``pace8x8-fft1024.bulk`` cell: its reference against the DFG
interpreter and against ``np.fft``, the cell end to end at a tiny size
(correct; the 16-bit control and the planted faults rejected), and the
two readers of the engine's work counters.

At the tiny size (``conftest.tiny``: 4 banks x 64 words) the cell runs a
16-point transform of the same builder, ``kernel_lib.fft_strided(16)``,
against the same reference."""
import json

import numpy as np
import pytest

from conftest import FAULTS, ROOT, faulty_traced, run_tiny, tiny

CELL = "pace8x8-fft1024.bulk"


def _config():
    return json.loads((ROOT / "chipbench/configs/pace8x8-fft1024.json")
                      .read_text())


def _bit_reversed(n):
    lg = n.bit_length() - 1
    return np.array([int(format(i, f"0{lg}b")[::-1], 2) for i in range(n)])


def _inputs(n, seed, images, q=256):
    """``images`` seeded images of an ``n``-point transform: samples as
    the configuration draws them, twiddles rounded to ``q`` steps per
    unit and held in Q8."""
    rng = np.random.default_rng(seed)
    k = np.arange(n // 2)
    row = {"wr": np.round(q * np.cos(2 * np.pi * k / n)) * (256 // q),
           "wi": np.round(-q * np.sin(2 * np.pi * k / n)) * (256 // q)}
    x = {name: rng.integers(-32768, 32768, (images, n))
         for name in ("xr", "xi")}
    x.update({name: np.tile(v, (images, 1)) for name, v in row.items()})
    return {name: v.astype(np.int32) for name, v in x.items()}


# -- the reference -------------------------------------------------------------

@pytest.mark.parametrize("n,images", [(16, 3), (64, 2), (1024, 1)])
def test_reference_matches_the_dfg_interpreter(n, images):
    from chipbench import harness
    from repro.core.dfg import interpret
    from repro.core.kernel_lib import fft_strided
    dfg, _, n_iters = fft_strided(n)
    assert n_iters == (n // 2) * (n.bit_length() - 1)
    x = _inputs(n, 100 + n, images)
    got = harness.load_reference("fft_strided").run(x, n_iters)
    for i in range(images):
        want = interpret(dfg, {k: v[i] for k, v in x.items()}, n_iters)
        for k in ("xr", "xi"):
            assert np.array_equal(got[k][i], want[k]), (n, k, i)


def _error_bound(n, wr, wi, x0_norm):
    """A bound on ``||fixed-point output - fft(x)/n||_2`` (bit-reversed).

    Scaled by 1/2, a radix-2 stage with exact unit twiddles is
    ``diag(1, w) H / 2`` per butterfly, ``H = [[1, 1], [1, -1]]``: its
    2-norm is 1/sqrt(2), so an error carried into a stage leaves it
    shrunk by 1/sqrt(2).  Each stage adds, over the exact stage:

    * twiddle error: the odd output is ``w' (a - b) / 2`` with the Q8
      twiddle ``w'``, off by at most ``eps = max |w' - w|`` times
      ``||(a - b) / 2|| <= ||x_s|| / sqrt(2)``;
    * truncation: each ``>> 1`` and ``>> 8`` floors both components, a
      complex error under sqrt(2); an even output takes one, an odd
      output one before the rotation (scaled by ``g = max |w'|``) and one
      after: ``||delta|| <= sqrt(n/2 * 2 + n/2 * ((g + 1) sqrt 2)^2)``.

    So ``E_{s+1} = E_s / sqrt2 + eps ||x_s|| / sqrt2 + ||delta||``, with
    ``||x_{s+1}|| <= g ||x_s|| / sqrt2 + ||delta||``."""
    k = np.arange(n // 2)
    w = (wr + 1j * wi) / 256
    eps = np.abs(w - np.exp(-2j * np.pi * k / n)).max()
    g = max(1.0, np.abs(w).max())
    r2 = np.sqrt(2)
    delta = np.sqrt(n / 2 * 2 + n / 2 * ((g + 1) * r2) ** 2)
    err, norm = 0.0, x0_norm
    for _ in range(n.bit_length() - 1):
        err = err / r2 + eps * norm / r2 + delta
        norm = g * norm / r2 + delta
    return err


def _fft_errors(n, q=256, shift=True):
    """Per image: the reference's distance from ``np.fft.fft(x)/n`` in
    bit-reversed order over the Q8 bound; ``q`` coarsens the twiddles,
    ``shift=False`` stands for a transform that lost its ``>> 1``."""
    from chipbench import harness
    images = 8
    x = _inputs(n, 7 + n, images, q)
    half = n // 2
    out = harness.load_reference("fft_strided").run(
        x, half * (n.bit_length() - 1))
    got = out["xr"] + 1j * out["xi"].astype(np.float64)
    if not shift:
        got = got * n                     # every stage's halving undone
    z = x["xr"] + 1j * x["xi"].astype(np.float64)
    want = (np.fft.fft(z, axis=1) / n)[:, _bit_reversed(n)]
    q8 = _inputs(n, 0, 1)
    bound = np.array([_error_bound(n, q8["wr"][0], q8["wi"][0],
                                   np.linalg.norm(z[i]))
                      for i in range(images)])
    return np.linalg.norm(got - want, axis=1) / bound


@pytest.mark.parametrize("n", [16, 64, 1024])
def test_reference_is_the_dft_within_the_fixed_point_bound(n):
    assert (_fft_errors(n) <= 1.0).all()
    # the bound is tight enough to see 4-bit twiddles, or no scaling
    assert (_fft_errors(n, q=16) > 1.0).all()
    assert (_fft_errors(n, shift=False) > 1.0).all()


def test_reference_refuses_a_partial_stage():
    from chipbench import harness
    ref = harness.load_reference("fft_strided")
    x = _inputs(16, 1, 1)
    with pytest.raises(ValueError, match="whole number"):
        ref.run(x, 12)
    with pytest.raises(ValueError, match="whole number"):
        ref.run(x, 40)
    assert np.array_equal(ref.run(x, 0)["xr"], x["xr"])


def test_the_16_bit_control_differs_on_the_configured_inputs():
    from chipbench import generator, harness
    cfg = _config()
    x = generator.make_inputs(cfg, 99, 8)
    assert x["wr"][0, 0] == 256 and x["wi"][0, 256] == -256
    ref = harness.load_reference(cfg["reference"])
    a, b = ref.run(x, cfg["n_iters"]), ref.run(x, cfg["n_iters"], bits=16)
    assert all((a[k] != b[k]).any(axis=1).all() for k in cfg["outputs"])


# -- the cell at a tiny size ---------------------------------------------------

@pytest.fixture
def tiny_fft(monkeypatch):
    """The cell with a 16-point transform of the same builder in 4 x 64
    words (the builder's kernel registered under a name of its own)."""
    from chipbench import harness
    from repro.core import kernel_lib
    monkeypatch.setitem(kernel_lib.KERNELS, "fft16",
                        lambda: kernel_lib.fft_strided(16))
    cell = tiny(harness.load_cell(CELL))
    cfg = dict(cell.config, kernel="fft16", n_iters=32,
               outputs={"xr": 16, "xi": 16})
    cfg["inputs"] = {
        "xr": dict(cfg["inputs"]["xr"], length=16),
        "xi": dict(cfg["inputs"]["xi"], length=16),
        "wr": dict(cfg["inputs"]["wr"], length=8, points=16),
        "wi": dict(cfg["inputs"]["wi"], length=8, points=16)}
    cell.config = cfg
    return cell


def test_cell_runs_correct_at_a_tiny_size(cpu_only, tiny_fft):
    res = run_tiny(tiny_fft)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["answers_compared"]["value"] > 0
    assert set(res["metrics"]) == {"setup_s", "samples_per_s"}


def test_the_control_is_not_correct(cpu_only, tiny_fft):
    res = run_tiny(tiny_fft, control=True)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(cpu_only, monkeypatch, tiny_fft,
                                      fault):
    from repro.ual.engine import KernelEngine
    monkeypatch.setattr(KernelEngine, "_traced", faulty_traced(fault))
    res = run_tiny(tiny_fft)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["wrong_answers"]["value"] > 0


# -- the two readers of the work counters --------------------------------------

def _trace(kernel_ns):
    from chipbench import xtrace
    k = "%cgra_exec.1 = s32[8192,128]{1,0} custom-call(%a)"
    ops, t = [], 0
    for d in kernel_ns:
        ops.append((k, t, t + d))
        t += d + 100
    return xtrace.Trace((0.0, float(t)),
                        [xtrace.DeviceTrace("/device:TPU:0", ops)], [])


def _ctx(trace, before, after):
    from chipbench import harness
    return harness.Context(cell=None, driver=None, trace=trace, spans=[],
                           before={"engine": before},
                           after={"engine": after}, peaks={})


def test_work_readers_take_the_window_increments():
    from chipbench import harness
    before = {"fabric_cycles": 1_000, "mem_passes": 50}
    after = {"fabric_cycles": 1_000 + 3 * 4_000_000,
             "mem_passes": 50 + 3 * 1_000}
    ctx = _ctx(_trace([2_000_000] * 3), before, after)      # 3 x 2 ms
    cycles = harness.load_reader("fabric_cycles_per_us.bulk")(ctx)
    assert cycles == pytest.approx(3 * 4_000_000 / 6_000.0)
    assert harness.load_reader("mem_pass_us.bulk")(ctx) == \
        pytest.approx(6_000.0 / 3_000)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                               # the parent
    ({"fabric_cycles": 5, "mem_passes": 5},
     {"fabric_cycles": 5, "mem_passes": 5}),                # no work
])
def test_work_readers_read_nothing_without_counts(before, after):
    from chipbench import harness
    ctx = _ctx(_trace([1_000]), before, after)
    for name in ("fabric_cycles_per_us.bulk", "mem_pass_us.bulk"):
        assert harness.load_reader(name)(ctx) is None, name


def test_work_readers_on_the_recorded_trace():
    """The recorded TPU trace of a bulk window (``data/``) with counts of
    160 passes and 80 x 128 cycles for each of its kernel calls."""
    from chipbench import harness, xtrace
    t = xtrace.load(xtrace.find_xplane(str(ROOT / "tests/chipbench/data")))
    evs = t.kernel_events()
    us = sum(e.end - e.start for e in evs) / 1e3
    ctx = _ctx(t, {"fabric_cycles": 0, "mem_passes": 0},
               {"fabric_cycles": 80 * 128 * len(evs),
                "mem_passes": 160 * len(evs)})
    assert harness.load_reader("fabric_cycles_per_us.bulk")(ctx) == \
        pytest.approx(80 * 128 * len(evs) / us)
    assert harness.load_reader("mem_pass_us.bulk")(ctx) == \
        pytest.approx(us / (160 * len(evs)))
