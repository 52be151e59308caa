"""The ``hycube4x4-gemm64.bulk`` cell: MachSuite's whole 64x64 gemm
(``kernel_lib.gemm_ncubed``, ``gemm64``) on HyCUBE 4x4.  Its reference
against the DFG interpreter and an exact matrix product, the cell end to
end at a tiny size (correct; the 16-bit control and the planted faults
rejected), and the engine's work counts per image at the configured size.

At the tiny size (``conftest.tiny``: 4 banks x 64 words) the cell runs
the 8 x 8 product of the same builder, ``kernel_lib.gemm_ncubed(8)``,
against the same reference."""
import json

import numpy as np
import pytest

from conftest import FAULTS, ROOT, faulty_traced, run_tiny, tiny

CELL = "hycube4x4-gemm64.bulk"


def _config():
    return json.loads((ROOT / "chipbench/configs/hycube4x4-gemm64.json")
                      .read_text())


def _reference(x, n_iters, bits=32):
    from chipbench import harness
    return harness.load_reference("gemm_ncubed").run(x, n_iters, bits=bits)


def _inputs(n, seed, images, lo=-128, hi=128):
    rng = np.random.default_rng(seed)
    return {k: rng.integers(lo, hi, (images, n * n)).astype(np.int32)
            for k in ("m1", "m2")}


# -- the reference -------------------------------------------------------------

@pytest.mark.parametrize("n,images", [(8, 4), (16, 3)])
def test_reference_matches_the_dfg_interpreter(n, images):
    from repro.core.dfg import interpret
    from repro.core.kernel_lib import gemm_ncubed
    dfg, _, n_iters = gemm_ncubed(n)
    assert n_iters == n ** 3 // 4
    x = _inputs(n, 200 + n, images)
    got = _reference(x, n_iters)
    for i in range(images):
        want = interpret(dfg, {k: v[i] for k, v in x.items()}, n_iters)
        assert np.array_equal(got["prod"][i], want["prod"]), (n, i)


@pytest.mark.parametrize("lo,hi", [(-128, 128), (-2 ** 31, 2 ** 31)])
def test_reference_is_the_wrapped_matrix_product(lo, hi):
    """At n = 64 the reference is the exact integer product wrapped to
    int32, with 8-bit operands (no wrap needed) and with full-width ones
    (every product wraps)."""
    x = _inputs(64, 5, 2, lo, hi)
    got = _reference(x, 64 ** 3 // 4)["prod"]
    for i in range(2):
        exact = (x["m1"][i].astype(object).reshape(64, 64)
                 @ x["m2"][i].astype(object).reshape(64, 64)).ravel()
        want = np.array([(int(v) + 2 ** 31) % 2 ** 32 - 2 ** 31
                         for v in exact], np.int64).astype(np.int32)
        assert np.array_equal(got[i], want), i


def test_reference_refuses_a_partial_product():
    x = _inputs(8, 1, 1)
    for n_iters in (0, 127, 129):
        with pytest.raises(ValueError, match="whole product"):
            _reference(x, n_iters)
    with pytest.raises(ValueError, match="whole product"):
        _reference({k: v[:, :60] for k, v in x.items()}, 128)


def test_the_16_bit_control_differs_on_the_configured_inputs():
    """A 64-term sum of 8-bit products spreads over about 44,000: on a
    16-bit datapath many of an image's 4,096 words wrap, in every image."""
    from chipbench import generator
    cfg = _config()
    x = generator.make_inputs(cfg, 2 ** 31 + 99, 16)
    assert x["m1"].shape == (16, 4096)
    assert x["m1"].min() >= -128 and x["m1"].max() < 128
    a = _reference(x, cfg["n_iters"])["prod"]
    b = _reference(x, cfg["n_iters"], bits=16)["prod"]
    assert (a != b).any(axis=1).all()


# -- the configuration ---------------------------------------------------------

def test_configuration_is_the_whole_product_in_16384_words():
    """The configuration's program, as the harness builds it, at the
    engine's counts per image: 9 passes an iteration (8 loads and a
    store), each over the 8 chunks of 512 rows its 4,096-word array
    covers; one live run of the three arrays, moved up and back."""
    from chipbench import harness
    from repro.core.lowering import mem_chunk_counts, mem_chunk_rows
    cfg = _config()
    program, _, exe = harness.compile_config(cfg)
    n_iters = cfg["n_iters"]
    assert n_iters == program.n_iters == 65_536
    assert program.layout.total_words == 16_384
    assert program.live == ((0, 12_288),)
    assert 2 * sum(n for _, n in program.live) == 24_576
    L = exe.lowered
    assert L.mem_slots == program.dfg.n_mem_ops == 9
    assert mem_chunk_rows(16_384) == 512
    bounded, dense = mem_chunk_counts(L, 16_384)
    assert (bounded, dense) == (9 * 8, 9 * 32)
    assert L.mem_slots * n_iters == 589_824
    assert bounded * n_iters == 8 * 589_824
    assert exe.II >= exe.map_result.mii == 3


def test_engine_counts_the_work_of_each_image():
    """The running engine's counters at n = 8 (the default layout, one
    chunk an array): one 8-image block, 128 iterations."""
    from repro import ual
    from repro.core.kernel_lib import gemm_ncubed
    from repro.core.lowering import kernel_rounds
    from repro.ual.engine import CompiledKernelCache
    dfg, mk, n_iters = gemm_ncubed(8)
    program = ual.Program.from_dfg(dfg, n_iters, make_mem=mk)
    exe = ual.compile(program, ual.Target.from_name(
        "hycube", backend="pallas", rows=4, cols=4))
    assert exe.success
    rng = np.random.default_rng(3)
    mems = [program.make_mem(rng) for _ in range(8)]
    cache = CompiledKernelCache(buckets=(8,))
    outs, _ = ual.backends.PallasBackend(engine=cache).execute_batch(
        program, None, mems, n_iters, lowered=exe.lowered)
    want = _reference({k: np.stack([m[k] for m in mems]) for k in mems[0]},
                      n_iters)["prod"]
    assert np.array_equal(np.stack([o["prod"] for o in outs]), want)
    L = exe.lowered
    stats = cache.engine_for(L).stats()
    assert stats["mem_passes"] == 9 * n_iters
    assert stats["mem_chunks"] == 9 * n_iters
    assert stats["transfer_words"] == 8 * 2 * 3 * 64
    assert stats["fabric_cycles"] == \
        8 * kernel_rounds(n_iters, L.II, L.t0_max) * L.II


# -- the cell at a tiny size ---------------------------------------------------

@pytest.fixture
def tiny_gemm(monkeypatch):
    """The cell with the 8 x 8 product of the same builder in 4 x 64
    words (the builder's kernel registered under a name of its own)."""
    from chipbench import harness
    from repro.core import kernel_lib
    monkeypatch.setitem(kernel_lib.KERNELS, "gemm8",
                        lambda: kernel_lib.gemm_ncubed(8))
    cell = tiny(harness.load_cell(CELL))
    cfg = dict(cell.config, kernel="gemm8", n_iters=128,
               outputs={"prod": 64})
    cfg["inputs"] = {k: dict(v, length=64)
                     for k, v in cfg["inputs"].items()}
    cell.config = cfg
    return cell


def test_cell_runs_correct_at_a_tiny_size(cpu_only, tiny_gemm):
    res = run_tiny(tiny_gemm)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["answers_compared"]["value"] > 0
    assert set(res["metrics"]) == {"setup_s", "samples_per_s"}


def test_the_control_is_not_correct(cpu_only, tiny_gemm):
    res = run_tiny(tiny_gemm, control=True)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def _one_prod_word_altered(real):
    """Word 0 of ``prod`` in the first image of every block, plus one."""
    from repro import ual

    def traced(self, niter, mem):
        base = ual.Program.from_kernel("gemm8", bank_words=64) \
            .layout.bases["prod"]
        return real(self, niter, mem).at[0, base].add(1)
    return traced


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["prod-word-altered"])
def test_planted_fault_is_not_correct(cpu_only, monkeypatch, tiny_gemm,
                                      fault):
    from repro.ual.engine import KernelEngine
    real = KernelEngine._traced
    monkeypatch.setattr(KernelEngine, "_traced",
                        _one_prod_word_altered(real)
                        if fault == "prod-word-altered"
                        else faulty_traced(fault))
    res = run_tiny(tiny_gemm)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["wrong_answers"]["value"] > 0
