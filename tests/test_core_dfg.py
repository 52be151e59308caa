"""DFG IR, builder, jaxpr extraction, interpreter, data layout."""
import numpy as np
import pytest

from repro.core.dfg import (DFGBuilder, apply_layout, flat_memory, interpret,
                            plan_layout, trace_into, unflatten_memory)
from repro.core.kernel_lib import KERNELS


@pytest.mark.parametrize("kname", sorted(KERNELS))
def test_kernels_build_and_interpret(kname):
    dfg, mk, n = KERNELS[kname]()
    rng = np.random.default_rng(0)
    out = interpret(dfg, mk(rng), n)
    for name in dfg.outputs:
        assert name in out
        assert out[name].dtype == np.int32
    assert dfg.topo_order()  # acyclic over dist==0 edges


def test_gemm_matches_numpy():
    dfg, mk, n = KERNELS["gemm"]()
    rng = np.random.default_rng(7)
    mem = mk(rng)
    out = interpret(dfg, mem, n)
    want = np.int32((mem["A"].astype(np.int64) * mem["B"].astype(np.int64)).sum())
    assert out["C"][0] == want


def test_nw_matches_reference_dp():
    dfg, mk, n = KERNELS["nw"]()
    rng = np.random.default_rng(3)
    mem = mk(rng)
    out = interpret(dfg, mem, n)
    left, row = 0, []
    for j in range(n):
        m = 1 if mem["seqa"][j] == mem["seqb"][j] else -1
        s = max(mem["above"][j] + m, mem["above"][j + 1] - 1, left - 1)
        left = s
        row.append(s)
    np.testing.assert_array_equal(out["row"], np.array(row, np.int32))


def test_jaxpr_extraction_matches_jax():
    import jax.numpy as jnp
    b = DFGBuilder("t")
    b.array("x", 8)
    b.array("y", 8, output=True)
    i = b.counter()
    x = b.load("x", i)

    def f(v):
        return jnp.where(v > 2, v * v - 1, v + 5) & 0xFF

    (o,) = trace_into(b, f, [x])
    b.store("y", i, o)
    dfg = b.build()
    rng = np.random.default_rng(0)
    xs = rng.integers(-10, 10, 8).astype(np.int32)
    out = interpret(dfg, {"x": xs}, 8)
    want = np.where(xs > 2, xs * xs - 1, xs + 5) & 0xFF
    np.testing.assert_array_equal(out["y"], want.astype(np.int32))


def test_recurrence_init_semantics():
    b = DFGBuilder("acc")
    b.array("out", 4, output=True)
    i = b.counter()
    acc = b.recur(init=100)
    acc2 = b.op("ADD", acc, 1)
    b.bind(acc, acc2)
    b.store("out", i, acc2)
    out = interpret(b.build(), {}, 4)
    np.testing.assert_array_equal(out["out"], [101, 102, 103, 104])


def _offset_copy():
    """``out[i + 2] = 3 * x[i + 1]`` and ``out[0] = x[5]``, the offsets
    given to ``load``/``store`` and folded into the nodes' consts."""
    b = DFGBuilder("offset_copy")
    b.array("x", 16)
    b.array("out", 16, output=True)
    i = b.counter()
    b.store("out", i, b.op("MUL", b.load("x", i, offset=1), 3), offset=2)
    b.store("out", 0, b.load("x", 3, offset=2))
    return b


def test_offset_load_and_store_fold_into_const():
    dfg = _offset_copy().build()
    mem = [(n.op, len(n.operands), n.const) for n in dfg.nodes
           if n.op in ("LOAD", "STORE")]
    assert mem == [("LOAD", 1, 1), ("STORE", 2, 2), ("LOAD", 0, 5),
                   ("STORE", 1, 0)]
    xs = np.arange(100, 116, dtype=np.int32)
    out = interpret(dfg, {"x": xs}, 8)
    want = np.zeros(16, np.int32)
    want[2:10] = 3 * xs[1:9]
    want[0] = xs[5]
    np.testing.assert_array_equal(out["out"], want)
    # no offset leaves the node as it was: an index and no const
    b = DFGBuilder("plain")
    b.array("x", 4)
    b.array("y", 4, output=True)
    i = b.counter()
    b.store("y", i, b.load("x", i))
    assert [n.const for n in b.build().nodes if n.op in ("LOAD", "STORE")] \
        == [None, None]


def test_offset_load_and_store_on_sim():
    """The folded offsets reach the cycle-accurate simulator through the
    layout's base, bit-exact against the interpreter."""
    from repro import ual
    program = ual.Program.from_builder(_offset_copy(), 8)
    exe = ual.compile(program, ual.Target.from_name(
        "hycube", backend="sim", rows=4, cols=4))
    assert exe.success
    xs = np.arange(-8, 8, dtype=np.int32) * 7
    (got,) = exe.run_batch([{"x": xs}], 8, backend="sim")
    want = interpret(program.dfg, {"x": xs}, 8)
    np.testing.assert_array_equal(got["out"], want["out"])
    assert got["out"][2] == 3 * xs[1] and got["out"][0] == xs[5]


def test_layout_round_robin_and_flat_roundtrip():
    dfg, mk, _ = KERNELS["fft"]()
    lay = plan_layout(dfg, n_banks=4, bank_words=512)
    banks = set(lay.banks.values())
    assert len(banks) > 1, "arrays should spread across banks"
    rng = np.random.default_rng(0)
    mem = mk(rng)
    flat = flat_memory(lay, mem)
    back = unflatten_memory(lay, flat, dfg.arrays)
    for k, v in mem.items():
        np.testing.assert_array_equal(back[k], v)


def test_layout_folds_bases_into_consts():
    dfg, _, _ = KERNELS["gemm"]()
    lay = plan_layout(dfg)
    laid = apply_layout(dfg, lay)
    for n, m in zip(dfg.nodes, laid.nodes):
        if n.op in ("LOAD", "STORE"):
            assert (m.const or 0) == (n.const or 0) + lay.bases[n.array]


def test_recurrence_cycles_found():
    dfg, _, _ = KERNELS["nw"]()
    cycles = dfg.recurrence_cycles()
    assert cycles, "nw has a left-cell recurrence"
