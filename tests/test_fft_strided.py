"""The whole strided FFT (``kernel_lib.fft_strided``, MachSuite
``fft/strided``) on PACE 8x8 through the normal path: it compiles clean,
the ``sim`` and ``pallas`` backends agree bit for bit with the plain
reference of the benchmark (``chipbench/references/fft_strided.py``),
and the mapped schedule never overlaps a stage's stores with the next
stage's loads of the same words."""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import ual
from repro.core.kernel_lib import fft_strided

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_EXES = {}


def _mapped(n):
    """``fft_strided(n)`` in 4 banks of 64 words, mapped once per module
    on PACE 8x8 (the benchmark's fabric)."""
    if n not in _EXES:
        dfg, mk, n_iters = fft_strided(n)
        program = ual.Program.from_dfg(dfg, n_iters, make_mem=mk,
                                       n_banks=4, bank_words=64)
        _EXES[n] = ual.compile(program, ual.Target.from_name(
            "pace", backend="sim", max_hops=4))
    return _EXES[n]


def _reference(mems, n_iters):
    from chipbench.references import fft_strided as ref
    batch = {k: np.stack([m[k] for m in mems]) for k in mems[0]}
    return ref.run(batch, n_iters)


@pytest.mark.parametrize("n,images,backends", [(16, 4, ("sim", "pallas")),
                                               (64, 2, ("sim",))])
def test_backends_match_the_reference(n, images, backends):
    exe = _mapped(n)
    assert exe.success and exe.check_report.ok
    rng = np.random.default_rng(n)
    mems = [exe.program.random_inputs(rng) for _ in range(images)]
    n_iters = exe.program.n_iters
    want = _reference(mems, n_iters)
    for backend in backends:
        outs = exe.run_batch(mems, n_iters, backend=backend)
        for i, out in enumerate(outs):
            for k in ("xr", "xi"):
                assert np.array_equal(out[k], want[k][i]), (backend, k, i)


def _store_load_distance(n):
    """The fewest iterations between two accesses of one word: a word
    stored by stage ``s`` is next read by stage ``s + 1``."""
    half, lg = n // 2, n.bit_length() - 2
    last, least = {}, None
    for t in range(half * (lg + 1)):
        stage, bf = t >> lg, t & (half - 1)
        span = half >> stage
        low = bf & (span - 1)
        odd = ((bf - low) << 1) | span | low
        for word in (odd ^ span, odd):
            if word in last:
                gap = t - last[word]
                least = gap if least is None else min(least, gap)
            last[word] = t
    return least


@pytest.mark.parametrize("n", [16, 64])
def test_iterations_in_flight_stay_within_the_store_load_distance(n):
    """Iteration ``t + d`` issues its first operation at cycle
    ``(t + d) II``, after iteration ``t``'s last (``t II + t0_max``),
    exactly when ``d II > t0_max``: when the iterations in flight,
    ``t0_max // II + 1``, are at most ``d``.  The mapper knows nothing of
    dependences through the scratchpad, so a schedule past that bound
    would read words before the previous stage has stored them."""
    d = _store_load_distance(n)
    assert d == n // 4
    lowered = _mapped(n).lowered
    in_flight = lowered.t0_max // lowered.II + 1
    assert in_flight <= d, (
        f"{in_flight} iterations in flight (II {lowered.II}, t0_max "
        f"{lowered.t0_max}) against a store->load distance of {d} through "
        f"the scratchpad: the mapper has no memory-dependence handling, so "
        f"this schedule would read words the previous stage has not "
        f"stored yet")


def test_fft1024_compiles_clean_on_pace():
    program = ual.Program.from_kernel("fft1024")
    assert program.n_iters == 512 * 10
    assert dict(program.arrays) == {"xr": 1024, "xi": 1024,
                                    "wr": 512, "wi": 512}
    assert set(program.outputs) == {"xr", "xi"}
    assert program.layout.total_words == 8192
    exe = ual.compile(program, ual.Target.from_name("pace",
                                                    backend="pallas"))
    assert exe.success and exe.check_report.ok
    assert exe.II >= exe.map_result.mii
    lowered = exe.lowered
    assert lowered.mem_slots == program.dfg.n_mem_ops == 10
    assert lowered.t0_max // lowered.II + 1 <= 1024 // 4


def _straddling_layout(n, how):
    """A layout whose arrays cross pass chunk boundaries: 4 banks of 500
    words (M 2,000, not a multiple of 512: 16-row chunks, which ``xi``
    and ``wi`` cross), or bases set by hand so that every array crosses
    a 512-row chunk boundary of M 4,096."""
    from repro.core.dfg import DataLayout, plan_layout
    dfg = fft_strided(n)[0]
    if how == "bank_words=500":
        return plan_layout(dfg, n_banks=4, bank_words=500)
    ends = {"xr": 512, "xi": 1536, "wr": 2048, "wi": 3072}
    return DataLayout({k: e - dfg.arrays[k] // 2 for k, e in ends.items()},
                      {k: b for b, k in enumerate(ends)}, 4, 1024)


@pytest.mark.parametrize("n,how", [(16, "bank_words=500"),
                                   (64, "straddle-512")])
def test_bounded_passes_cross_stages_bit_exact(n, how):
    """The in-place read-modify-write crosses stages through the
    scratchpad; with each pass bounded to the chunks its array covers
    (and arrays that straddle a chunk boundary) the kernel stays
    bit-exact against the reference and the simulator."""
    from repro.core.lowering import mem_chunk_counts, mem_chunk_rows
    dfg, mk, n_iters = fft_strided(n)
    layout = _straddling_layout(n, how)
    program = ual.Program(dfg, layout, n_iters, mk)
    M = layout.total_words
    chunk = mem_chunk_rows(M)
    spans = {name: (lo + dfg.arrays[name] - 1) // chunk - lo // chunk + 1
             for name, lo in layout.bases.items()}
    assert spans["xi"] == spans["wi"] == 2
    exe = ual.compile(program, ual.Target.from_name(
        "pace", backend="pallas", max_hops=4))
    assert exe.success and exe.check_report.ok
    L = exe.lowered
    assert L.t0_max // L.II + 1 <= n // 4
    # xr and xi: two LOADs and two STOREs each; wr and wi: one LOAD each
    bounded = 4 * (spans["xr"] + spans["xi"]) + spans["wr"] + spans["wi"]
    assert mem_chunk_counts(L, M) == (bounded, 10 * (M // chunk))
    rng = np.random.default_rng(n + 1)
    mems = [program.random_inputs(rng) for _ in range(3)]
    want = _reference(mems, n_iters)
    for backend in ("pallas", "sim"):
        outs = exe.run_batch(mems, n_iters, backend=backend)
        for i, out in enumerate(outs):
            for k in ("xr", "xi"):
                assert np.array_equal(out[k], want[k][i]), (backend, k, i)


def test_fft1024_passes_cover_18_of_160_chunks():
    """At M = 8192 each of fft1024's 1,024-word arrays covers two of the
    16 chunks of 512 rows and each twiddle array one: its 10 LOAD/STORE
    slots (``xr`` and ``xi`` two loads and two stores each, ``wr`` and
    ``wi`` one load) run 18 chunk steps an iteration instead of 160."""
    from repro.core.lowering import mem_chunk_counts
    program = ual.Program.from_kernel("fft1024")
    exe = ual.compile(program, ual.Target.from_name("pace",
                                                    backend="pallas"))
    assert exe.success
    assert mem_chunk_counts(exe.lowered, 8192) == (18, 160)
    assert 18 * program.n_iters == 92_160


def test_builder_refuses_a_size_that_is_not_a_power_of_two():
    for n in (4, 12, 1000):
        with pytest.raises(ValueError, match="power of two"):
            fft_strided(n)
