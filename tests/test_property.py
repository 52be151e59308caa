"""Property-based tests (hypothesis) on the system's invariants.

The central invariant is Morpher's own correctness contract: for ANY
loop-body DFG the flow  map -> emit config -> simulate  must agree
bit-exactly with the DFG interpreter, and the Pallas cgra_exec kernel must
agree with the simulator.  Hypothesis generates random DFGs (random ALU
dags + loads/stores + optional recurrences) to hunt corner cases the fixed
kernel library misses.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.adl import hycube
from repro.core.dfg import (DFGBuilder, apply_layout, flat_memory, interpret,
                            plan_layout, unflatten_memory)
from repro.core.mapper import compute_mii, map_dfg

ALU2 = ("ADD", "SUB", "MUL", "AND", "OR", "XOR", "MIN", "MAX",
        "CMPLT", "CMPGT")


@st.composite
def random_dfg(draw):
    """A random loop body: loads, an ALU dag, optional recurrence, stores."""
    b = DFGBuilder("prop")
    n_in = draw(st.integers(1, 3))
    N = 8
    for j in range(n_in):
        b.array(f"in{j}", N)
    b.array("out", N, output=True)
    i = b.counter()
    vals = [b.load(f"in{j}", i) for j in range(n_in)]
    use_rec = draw(st.booleans())
    rec = None
    if use_rec:
        rec = b.recur(init=draw(st.integers(-4, 4)))
        vals.append(rec)
    n_ops = draw(st.integers(1, 6))
    for _ in range(n_ops):
        op = draw(st.sampled_from(ALU2))
        a = vals[draw(st.integers(0, len(vals) - 1))]
        use_const = draw(st.booleans())
        if use_const:
            v = b.op(op, a, const=draw(st.integers(-8, 8)))
        else:
            c = vals[draw(st.integers(0, len(vals) - 1))]
            v = b.op(op, a, c)
        vals.append(v)
    result = vals[-1]
    if use_rec:
        # keep recurrence values bounded so MUL chains cannot overflow-diverge
        bounded = b.op("MAX", b.op("MIN", result, 1 << 10), -(1 << 10))
        b.bind(rec, bounded)
        result = bounded
    b.store("out", i, result)
    return b.build()


@settings(max_examples=12, deadline=None)
@given(random_dfg(), st.integers(0, 3))
def test_mapped_config_matches_interpreter(dfg, seed):
    """map -> simulate == interpret, for arbitrary DFGs (bit-exact)."""
    from repro.core.simulator import simulate
    fab = hycube(4, 4)
    layout = plan_layout(dfg)
    laid = apply_layout(dfg, layout)
    res = map_dfg(laid, fab, seed=seed, ii_max=24)
    assert res.success, "mapper must map any small DFG within ii_max"
    assert res.II >= compute_mii(laid, fab)
    rng = np.random.default_rng(seed)
    mem = {k: rng.integers(-50, 50, n).astype(np.int32)
           for k, n in dfg.arrays.items() if k != "out"}
    n_iters = 8
    expect = interpret(dfg, mem, n_iters)
    flat = flat_memory(layout, mem)
    out, _ = simulate(res.config, flat, n_iters)
    got = unflatten_memory(layout, out, dfg.arrays)
    np.testing.assert_array_equal(got["out"], expect["out"])


@settings(max_examples=6, deadline=None)
@given(random_dfg(), st.sampled_from(["hycube", "n2n", "pace"]),
       st.integers(1, 4), st.integers(2, 8))
def test_batched_engine_matches_reference_and_oracle(dfg, fab_name, B,
                                                     n_iters):
    """Engine parity, for arbitrary DFGs: vectorized-batched ==
    scalar reference == DFG-interpreter oracle, bit-exactly, across
    fabrics (incl. HyCUBE multi-hop bypass chains and PACE's 8x8 array),
    batch sizes and trip counts."""
    from repro.core.adl import n2n, pace
    from repro.core.lowering import link_config
    from repro.core.simulator import simulate_batch, simulate_reference
    fab = {"hycube": lambda: hycube(4, 4), "n2n": lambda: n2n(4, 4),
           "pace": pace}[fab_name]()
    layout = plan_layout(dfg, n_banks=fab.n_mem_ports)
    laid = apply_layout(dfg, layout)
    res = map_dfg(laid, fab, seed=0, ii_max=24)
    assert res.success, f"mapper must map any small DFG on {fab.name}"
    linked = link_config(res.config)
    rng = np.random.default_rng(7)
    named = [{k: rng.integers(-50, 50, n).astype(np.int32)
              for k, n in dfg.arrays.items() if k != "out"}
             for _ in range(B)]
    flats = np.stack([flat_memory(layout, m) for m in named])
    outs, stats = simulate_batch(linked, flats, n_iters)
    for b in range(B):
        want, rstats = simulate_reference(res.config, flats[b], n_iters)
        np.testing.assert_array_equal(outs[b], want)
        got = unflatten_memory(layout, outs[b], dfg.arrays)
        expect = interpret(dfg, named[b], n_iters)
        np.testing.assert_array_equal(got["out"], expect["out"])
    assert (stats.fired, stats.idle_slots, stats.max_mem_ports_used) == \
           (rstats.fired, rstats.idle_slots, rstats.max_mem_ports_used)


@settings(max_examples=10, deadline=None)
@given(random_dfg(), st.integers(0, 3))
def test_verifier_clean_implies_executable(dfg, seed):
    """The static verifier's soundness direction, for arbitrary DFGs: a
    mapper-produced config never carries ERROR findings (the mapper never
    emits the hazards the verifier hunts), and an error-free config must
    execute without the engines' runtime checks firing.  Warnings are
    allowed only for dead code (UAL007): the random generator freely
    builds ops whose results nothing consumes, and the mapper faithfully
    maps them — a true positive, not verifier noise."""
    from repro.analysis.verifier import verify
    from repro.core.lowering import link_config
    from repro.core.simulator import simulate_reference
    fab = hycube(4, 4)
    layout = plan_layout(dfg, n_banks=fab.n_mem_ports)
    laid = apply_layout(dfg, layout)
    res = map_dfg(laid, fab, seed=seed, ii_max=24)
    assert res.success
    linked = link_config(res.config)
    rep = verify(cfg=res.config, linked=linked)
    assert rep.ok, rep.render()
    assert {d.code for d in rep.warnings} <= {"UAL007"}, rep.render()
    assert linked.unresolved_inputs == 0
    rng = np.random.default_rng(seed)
    mem = {k: rng.integers(-50, 50, n).astype(np.int32)
           for k, n in dfg.arrays.items() if k != "out"}
    # clean verdict => the runtime port/hazard checks stay silent
    simulate_reference(res.config, flat_memory(layout, mem), 8,
                       check_ports=True)


@settings(max_examples=6, deadline=None)
@given(random_dfg())
def test_pallas_kernel_matches_simulator(dfg):
    """linked cgra_exec == cycle-accurate simulator, over a random batch."""
    from repro.kernels.cgra_exec.ops import cgra_exec_op
    from repro.kernels.cgra_exec.ref import cgra_exec_ref
    fab = hycube(4, 4)
    layout = plan_layout(dfg)
    laid = apply_layout(dfg, layout)
    res = map_dfg(laid, fab, seed=0, ii_max=24)
    assert res.success
    rng = np.random.default_rng(1)
    mems = np.stack([
        flat_memory(layout, {k: rng.integers(-50, 50, n).astype(np.int32)
                             for k, n in dfg.arrays.items()})
        for _ in range(2)])
    got = cgra_exec_op(res.config, mems, 6)
    want = cgra_exec_ref(res.config, mems, 6)
    np.testing.assert_array_equal(got, want)
