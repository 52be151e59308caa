"""Persistent JIT execution engine: trace-once/run-many for the pallas path.

The contract under test:

  * batch-bucket padding is semantically invisible — batch sizes that
    straddle bucket boundaries (1, 7, 9, 33, 129) are bit-exact against
    the scalar reference engine and the unpadded DFG-interpreter oracle,
  * the trace counter does not grow with repeated same-bucket calls
    (monkeypatch-counted on the shared ``make_cgra_call`` constructor),
    and stays O(#buckets) under mixed-size traffic,
  * ``n_iters`` is traced: one warm trace serves every iteration count,
  * ``Executable.warmup(buckets=...)`` pre-traces the ladder and records
    engine stats in ``last_info``,
  * external ``cgra_exec_op(..., linked=None)`` callers never lower the
    same configuration twice (the fingerprint memo),
  * ``Program.flatten_batch``/``unflatten_batch`` match the per-sample
    scalar paths exactly (including missing / short arrays),
  * ``Service.stats()`` surfaces the engine aggregate,
  * packed blocks: the pallas backend moves only the program's declared
    arrays (``Program.live``), and every packed entry — batch with and
    without ``flats=``, stream, the sharded engine — matches the
    full-width ``KernelEngine.run`` and the oracle on every published
    deployment; ``transfer_words`` counts the words moved.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from deployments import DEPLOYMENTS, FABRICS
from repro import ual
from repro.core.dfg import interpret
from repro.core.simulator import simulate_reference
from repro.launch.mesh import forced_device_env
from repro.ual.engine import CompiledKernelCache, bucket_ladder

N_ITERS = 6


@pytest.fixture(scope="module")
def compiled():
    """One small-scratchpad gemm compile shared by the module (smaller
    bank_words keep the interpret-mode traces cheap)."""
    program = ual.Program.from_kernel("gemm", bank_words=64)
    target = ual.Target.from_name("hycube", rows=4, cols=4,
                                  backend="pallas")
    exe = ual.compile(program, target)
    assert exe.success
    return program, exe


def _mems(program, B, seed=0):
    rng = np.random.default_rng(seed)
    return [program.random_inputs(rng) for _ in range(B)]


# ---------------------------------------------------------------------------
# bucket-padding correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 7, 9, 33, 129])
def test_bucket_straddling_batches_bitexact(compiled, B):
    """Sizes straddling every bucket boundary of the (1, 8, 32, 128)
    ladder — including B=129, which runs as a warm largest-bucket chunk
    plus a bucket-1 tail — are bit-exact vs the unpadded oracle, and
    (spot-checked first/last sample) vs the scalar reference engine."""
    program, exe = compiled
    mems = _mems(program, B, seed=B)
    outs = exe.run_batch(mems, n_iters=N_ITERS)
    assert exe.last_info["batch"] == B
    for m, got in zip(mems, outs):
        want = interpret(program.dfg, m, N_ITERS)
        for name in program.outputs:
            np.testing.assert_array_equal(got[name], want[name])
    for b in (0, B - 1):
        flat = program.flatten(mems[b])
        ref, _ = simulate_reference(exe.map_result.config, flat, N_ITERS)
        refd = program.unflatten(ref)
        for name in program.outputs:
            np.testing.assert_array_equal(outs[b][name], refd[name])


def test_dynamic_n_iters_shares_one_trace(compiled):
    """The trip count is a traced scalar: different n_iters on one bucket
    reuse the same trace, and each still matches the oracle."""
    program, exe = compiled
    be = ual.get_backend("pallas")
    eng = be.engine.engine_for(exe.lowered, lanes=be.lanes)
    mems = _mems(program, 4, seed=42)
    exe.run_batch(mems, n_iters=3)           # warm (or reuse) bucket 8
    before = eng.traces
    for n in (1, 5, 11):
        outs = exe.run_batch(mems, n_iters=n)
        for m, got in zip(mems, outs):
            want = interpret(program.dfg, m, n)
            for name in program.outputs:
                np.testing.assert_array_equal(got[name], want[name])
    assert eng.traces == before


# ---------------------------------------------------------------------------
# trace accounting
# ---------------------------------------------------------------------------

def test_trace_counter_static_across_same_bucket_calls(compiled,
                                                       monkeypatch):
    """Repeated calls landing in one bucket must not grow the trace
    counter — proved by counting invocations of the ``pallas_call``
    constructor (which runs exactly once per trace)."""
    import repro.ual.engine as engine_mod

    program, exe = compiled
    builds = []
    real = engine_mod.make_cgra_call
    monkeypatch.setattr(engine_mod, "make_cgra_call",
                        lambda *a, **k: builds.append(1) or real(*a, **k))

    cache = CompiledKernelCache()            # fresh: no warm traces
    flats = program.flatten_batch(_mems(program, 8, seed=7))
    for B in (3, 8, 1, 5, 8, 2, 7, 4):       # buckets: {8, 1}
        out, info = cache.run(exe.lowered, flats[:B], N_ITERS)
        assert out.shape == (B, program.layout.total_words)
    eng = cache.engine_for(exe.lowered)
    assert len(builds) == 2                  # one per distinct bucket
    assert eng.traces == 2
    assert set(eng.bucket_calls) == {1, 8}
    assert eng.stats()["hit_ratio"] == pytest.approx(6 / 8)


def test_mixed_size_traffic_traces_bounded_by_ladder(compiled):
    """O(#buckets) traces no matter how traffic is shaped: 40 mixed-size
    calls on a fresh engine trace at most once per ladder bucket."""
    program, exe = compiled
    cache = CompiledKernelCache(buckets=(1, 4, 8))
    flats = program.flatten_batch(_mems(program, 8, seed=11))
    for i in range(40):
        B = 1 + i % 8
        cache.run(exe.lowered, flats[:B], N_ITERS)
    eng = cache.engine_for(exe.lowered)
    assert eng.buckets == (1, 4, 8)
    assert eng.traces <= len(eng.buckets)
    agg = cache.stats()
    assert agg["engines"] == 1 and agg["traces"] == eng.traces


def test_warmup_pre_traces_the_ladder(compiled):
    program, exe = compiled
    cache = CompiledKernelCache()
    prev = ual.set_default_engine(cache)
    try:
        stats = exe.warmup(buckets=(1, 8))
        assert stats["traces"] == 2
        assert exe.last_info["engine_stats"]["traces"] == 2
        exe.run_batch(_mems(program, 5, seed=3), n_iters=N_ITERS)
        assert exe.last_info["traced"] == 0    # warm bucket, no retrace
        assert exe.last_info["engine"] == "pallas-jit"
    finally:
        ual.set_default_engine(prev)


def test_bucket_ladder_validation():
    assert bucket_ladder(128) == (1, 8, 32, 128)
    assert bucket_ladder(16, (32, 4, 4, 1)) == (1, 4)   # capped + deduped
    with pytest.raises(ValueError):
        bucket_ladder(8, (16, 32))


# ---------------------------------------------------------------------------
# no path lowers one config twice
# ---------------------------------------------------------------------------

def test_cgra_exec_op_memoizes_lowering(compiled, monkeypatch):
    """External callers passing ``linked=None`` ride the per-process
    fingerprint memo instead of silently re-lowering per call."""
    import repro.kernels.cgra_exec.ops as ops

    program, exe = compiled
    ops._LINKED_MEMO.clear()
    lowers = []
    real = ops.link_config
    monkeypatch.setattr(ops, "link_config",
                        lambda cfg: lowers.append(1) or real(cfg))
    flats = program.flatten_batch(_mems(program, 2, seed=9))
    a = ops.cgra_exec_op(exe.map_result.config, flats, N_ITERS)
    b = ops.cgra_exec_op(exe.map_result.config, flats, N_ITERS)
    assert len(lowers) == 1
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# vectorized flatten/unflatten
# ---------------------------------------------------------------------------

def test_flatten_batch_matches_scalar_paths(compiled):
    program, _ = compiled
    mems = _mems(program, 5, seed=13)
    flats = program.flatten_batch(mems)
    want = np.stack([program.flatten(m) for m in mems])
    np.testing.assert_array_equal(flats, want)
    unflat = program.unflatten_batch(flats)
    for b, m in enumerate(unflat):
        scalar = program.unflatten(flats[b])
        assert set(m) == set(scalar)
        for name in m:
            np.testing.assert_array_equal(m[name], scalar[name])


def test_flatten_batch_ragged_and_missing_arrays(compiled):
    """Missing arrays zero-fill and short arrays zero-pad, exactly like
    the scalar path."""
    program, _ = compiled
    rng = np.random.default_rng(17)
    full = program.random_inputs(rng)
    name = program.inputs[0]
    short = dict(full)
    short[name] = full[name][: max(1, len(full[name]) // 2)]
    missing = {k: v for k, v in full.items() if k != name}
    mems = [full, short, missing]
    flats = program.flatten_batch(mems)
    want = np.stack([program.flatten(m) for m in mems])
    np.testing.assert_array_equal(flats, want)


def test_flatten_batch_rejects_unknown_arrays(compiled):
    program, _ = compiled
    with pytest.raises(KeyError, match="unknown array"):
        program.flatten_batch([{"nope": np.zeros(4, np.int32)}])


# ---------------------------------------------------------------------------
# service surface
# ---------------------------------------------------------------------------

def test_service_stats_surface_engine_aggregate():
    svc = ual.Service(start=False)
    try:
        snap = svc.stats()
        assert "engine" in snap
        assert {"engines", "traces", "hit_ratio"} <= set(snap["engine"])
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# platform-chosen execution mode, kernel tables, compile cache placement
# ---------------------------------------------------------------------------

def test_engine_mode_and_platform_follow_jax_backend(compiled):
    """No caller picks interpret mode: off a TPU every engine interprets
    the kernel, and its stats say so."""
    import jax

    from repro.kernels.cgra_exec.kernel import interpret_mode
    _, exe = compiled
    cache = CompiledKernelCache()
    stats = cache.engine_for(exe.lowered).stats()
    assert interpret_mode() == (jax.default_backend() != "tpu")
    assert stats["mode"] == ("interp" if interpret_mode() else "tpu")
    assert stats["platform"] == jax.default_backend()
    name, = cache.stats()["per_engine"]
    assert name.endswith("/" + stats["mode"])


@pytest.mark.parametrize("II", [1, 2, 3, 5])
def test_kernel_tables_iteration_index_is_a_subtraction(II):
    """``q0`` in the kernel tables turns ``(t - t0) // II`` into ``q - q0``
    for ``t = q*II + s`` — for every slot, including ``t0`` scheduled
    off its own slot."""
    from repro.core.lowering import KV_Q0, LinkedConfig, kernel_tables
    P = 1
    t0s = np.arange(0, 4 * II)
    for t0 in t0s:
        scalar = np.zeros((II, P, 4), np.int32)
        scalar[:, :, 0] = 1                        # any non-NOP opcode
        scalar[:, :, 3] = t0
        linked = LinkedConfig(
            II=II, n_pes=P, n_regs=1, mem_pes=(), scalar=scalar,
            ops=np.zeros((II, P, 3, 5), np.int32),
            regw=np.zeros((II, P, 1, 3), np.int32))
        _, vtab, _ = kernel_tables(linked)
        for s in range(II):
            q0 = vtab[s, 0, KV_Q0]
            for q in range(6):
                t = q * II + s
                assert q - q0 == (t - t0) // II, (II, t0, s, q)


@pytest.mark.parametrize("kernel_name,fabric", DEPLOYMENTS)
def test_copy_lists_match_dense_gather(kernel_name, fabric):
    """The kernel's row-copy lists (``ctab``) move exactly what a dense
    one-hot gather over the whole PE state selects: every operand (an
    absent source reads 0), every register row (moved, written from a
    result that fired, or kept), at every round around the firing window.
    The counter's two numbers are the lists' length and the dense scan's
    steps."""
    from repro.core.lowering import (K_O, K_R, K_RESULT, KC_HEAD, KC_MOVES,
                                     KC_OPS, KC_RES, KC_WIDTH, KV_LIVE,
                                     KV_Q0, kernel_tables, state_copy_counts)
    exe = ual.compile(ual.Program.from_kernel(kernel_name),
                      ual.Target.from_name(fabric, backend="pallas",
                                           **FABRICS[fabric]))
    assert exe.success
    L = exe.lowered
    S, P, R = L.II, L.n_pes, L.n_regs
    N, B, n_iters = P + P * R, 3, 2
    _, vtab, ctab = kernel_tables(L)
    rng = np.random.default_rng(15)
    st = rng.integers(1, 2 ** 31, (N + P, B)).astype(np.int32)  # [O; R; res]

    def source(kind, pe, reg):
        return np.where(kind == K_O, pe,
                        np.where(kind == K_R, P + pe * R + reg, -1))

    def gather(idx, rows):
        out = np.zeros((len(idx), B), np.int32)
        for r in range(len(rows)):
            out = np.where((idx == r)[:, None], rows[r], out)
        return out

    head = ctab[:S * KC_HEAD].reshape(S, 3, 2)

    def entries(s, lst):
        n, at = head[s, lst]
        return ctab[at:at + n * KC_WIDTH[lst]].reshape(n, KC_WIDTH[lst])

    routed = absent = kept = 0
    for s in range(S):
        opnd = np.zeros((3 * P, B), np.int32)
        for dst, row in entries(s, KC_OPS):
            opnd[dst] = st[row]
        for k in range(3):
            idx = source(*(L.ops[s, :, k, i] for i in range(3)))
            np.testing.assert_array_equal(opnd[k * P:(k + 1) * P],
                                          gather(idx, st[:N]))
            routed += int((idx >= 0).sum())
            absent += int((idx < 0).sum())
        rk, rp, rr = L.regw[s].reshape(P * R, 3).T
        move = source(rk, rp, rr)
        is_res = rk == K_RESULT
        moved = st[P:N].copy()
        for dst, row in entries(s, KC_MOVES):
            moved[dst] = st[row]
        routed += int((move >= 0).sum() + is_res.sum())
        kept += int(((move < 0) & ~is_res).sum())
        live, q0 = vtab[s, rp, KV_LIVE] != 0, vtab[s, rp, KV_Q0]
        for q in range(int(q0.min()) - 1, int(q0.max()) + n_iters + 1):
            got = moved.copy()
            for dst, pe, e_live, e_q0 in entries(s, KC_RES):
                if e_live and 0 <= q - e_q0 < n_iters:
                    got[dst] = st[N + pe]
            want = np.where((move >= 0)[:, None], gather(move, st[:N]),
                            st[P:N])
            fired = is_res & live & (q - q0 >= 0) & (q - q0 < n_iters)
            want = np.where(fired[:, None],
                            gather(np.where(is_res, rp, -1), st[N:]), want)
            np.testing.assert_array_equal(got, want)
    assert absent and kept          # both defaults are exercised
    assert state_copy_counts(L) == (routed, S * (4 * N + P))
    assert int(head[..., 0].sum()) == routed


def test_engine_stats_report_state_copies(compiled):
    """``KernelEngine.stats()`` reports the copy counter, so the
    ``engine`` source of the metrics registry carries it."""
    from repro.core.lowering import state_copy_counts
    _, exe = compiled
    stats = CompiledKernelCache().engine_for(exe.lowered).stats()
    copied, dense = state_copy_counts(exe.lowered)
    assert stats["state_rows_copied_per_round"] == copied
    assert stats["state_rows_dense_per_round"] == dense
    assert 0 < copied < dense


def test_engine_counts_the_kernels_work(compiled):
    """``fabric_cycles``: the kernel's rounds of II cycles (the whole
    schedule, ``total_cycles``, in whole rounds) for every real image;
    ``mem_passes``: one scratchpad pass per LOAD/STORE node, iteration
    and kernel call.  ``run`` and ``run_stream`` both count, and the
    cache sums its engines."""
    from repro.core.lowering import kernel_rounds
    program, exe = compiled
    L = exe.lowered
    cache = CompiledKernelCache()
    eng = cache.engine_for(L)
    M = program.layout.total_words
    eng.run(np.zeros((5, M), np.int32), N_ITERS)               # 1 block
    list(eng.run_stream(np.zeros((20, M), np.int32), N_ITERS,
                        chunk=8))                              # 3 blocks
    rounds = kernel_rounds(N_ITERS, L.II, L.t0_max)
    assert rounds == -(-L.total_cycles(N_ITERS) // L.II)
    assert L.mem_slots == program.dfg.n_mem_ops == 9
    stats = eng.stats()
    assert stats["fabric_cycles"] == rounds * L.II * (5 + 20)
    assert stats["mem_passes"] == 9 * N_ITERS * 4
    total = cache.stats()
    assert (total["fabric_cycles"], total["mem_passes"]) == \
        (stats["fabric_cycles"], stats["mem_passes"])


# ---------------------------------------------------------------------------
# scratchpad passes bounded to their arrays' rows
# ---------------------------------------------------------------------------

#: the benchmark's two short deployments at the default layout
#: (M = 8192, 512-row chunks): (kernel, fabric, chunk steps per iteration
#: bounded and dense)
BOUNDED = {"gemm": ("hycube", dict(rows=4, cols=4), 9, 144),
           "fft": ("pace", {}, 10, 160)}


def _default_layout(kernel_name):
    fabric, kw, _, _ = BOUNDED[kernel_name]
    program = ual.Program.from_kernel(kernel_name)
    exe = ual.compile(program, ual.Target.from_name(fabric, backend="pallas",
                                                    **kw))
    assert exe.success and program.layout.total_words == 8192
    return program, exe


@pytest.mark.parametrize("kernel_name", sorted(BOUNDED))
def test_mem_rows_are_the_accessed_arrays(kernel_name):
    """Each scheduled LOAD/STORE slot's row range is its node's array
    under the program's layout, every other slot's the whole scratchpad;
    the chunk steps per iteration are exact."""
    from repro.core.lowering import MEM_ROWS_END, mem_chunk_counts
    program, exe = _default_layout(kernel_name)
    L, cfg, laid = exe.lowered, exe.map_result.config, program.laid
    bases, arrays = program.layout.bases, program.dfg.arrays
    mem_slots = 0
    for s in range(L.II):
        for j, p in enumerate(L.mem_pes):
            nid = int(cfg.node_id[s, p])
            node = laid.nodes[nid] if nid >= 0 else None
            if node is not None and node.op in ("LOAD", "STORE"):
                mem_slots += 1
                lo = bases[node.array]
                want = [lo, lo + arrays[node.array]]
            else:
                want = [0, MEM_ROWS_END]
            assert L.mem_rows[s, j].tolist() == want, (s, p)
    assert mem_slots == L.mem_slots
    _, _, bounded, dense = BOUNDED[kernel_name]
    assert mem_chunk_counts(L, 8192) == (bounded, dense)


def test_no_layout_keeps_the_whole_scratchpad():
    """``link_config(cfg)`` alone — the one-shot wrapper's and
    ``cgra_exec_op``'s lowering — bounds nothing: every pass walks all
    chunks, the tables hash apart from the bounded ones, and both give
    the same images, bit-exact against the simulator."""
    from repro.core.lowering import (MEM_ROWS_END, link_config,
                                     lowered_fingerprint, mem_chunk_counts)
    from repro.core.simulator import simulate_batch
    program, exe = _default_layout("gemm")
    full = link_config(exe.map_result.config)
    assert (full.mem_rows[..., 0] == 0).all()
    assert (full.mem_rows[..., 1] == MEM_ROWS_END).all()
    assert mem_chunk_counts(full, 8192) == (144, 144)
    assert lowered_fingerprint(full) != lowered_fingerprint(exe.lowered)
    flats = program.flatten_batch(_mems(program, 3, seed=4))
    cache = CompiledKernelCache()
    got_full, _ = cache.run(full, flats, N_ITERS, lanes=8)
    got_bounded, _ = cache.run(exe.lowered, flats, N_ITERS, lanes=8)
    want, _ = simulate_batch(exe.lowered, flats, N_ITERS)
    np.testing.assert_array_equal(got_full, want)
    np.testing.assert_array_equal(got_bounded, want)


def test_out_of_array_access_loads_zero_and_stores_nothing():
    """The documented edge of the bounded passes: a LOAD past its array,
    into another array's chunks, reads 0, and such a STORE writes
    nothing, as an address outside ``[0, M)`` always did.  The simulator
    indexes the flat scratchpad, so there the same program reads and
    writes the neighbour: such a program is ill-formed."""
    from repro.core.dfg import DFGBuilder
    from repro.core.lowering import link_config
    from repro.core.simulator import simulate_batch
    n, bank = 4, 2048
    b = DFGBuilder("past_the_array")
    for name in ("A", "B", "C", "D"):
        b.array(name, n)
    i = b.counter()
    past = b.op("ADD", i, const=bank)          # one bank past its array
    b.store("C", i, b.load("A", past))         # reads B[i]
    b.store("C", past, b.op("ADD", i, const=100))   # writes D[i]
    program = ual.Program.from_builder(b, n, n_banks=4, bank_words=bank)
    exe = ual.compile(program, ual.Target.from_name(
        "hycube", rows=4, cols=4, backend="pallas"))
    assert exe.success
    rng = np.random.default_rng(2)
    mem = {k: rng.integers(1, 50, n).astype(np.int32) for k in "ABCD"}
    flats = program.flatten_batch([mem])
    cache = CompiledKernelCache()
    bounded = program.unflatten(cache.run(exe.lowered, flats, n)[0][0])
    np.testing.assert_array_equal(bounded["C"], 0)
    np.testing.assert_array_equal(bounded["D"], mem["D"])
    # the whole-scratchpad tables and the simulator reach the neighbours
    full = link_config(exe.map_result.config)
    for out in (cache.run(full, flats, n)[0][0],
                simulate_batch(exe.lowered, flats, n)[0][0]):
        named = program.unflatten(out)
        np.testing.assert_array_equal(named["C"], mem["B"])
        np.testing.assert_array_equal(named["D"], np.arange(n) + 100)


def test_engine_counts_the_chunk_steps():
    """``mem_chunks``: per kernel call, each fired LOAD/STORE slot's chunk
    span times ``n_iters`` — 9 chunk steps an iteration for gemm on
    HyCUBE 4x4 at M = 8192, where unbounded passes would run 144.  One
    streamed block and one ``run`` call; the cache sums its engines."""
    program, exe = _default_layout("gemm")
    cache = CompiledKernelCache()
    eng = cache.engine_for(exe.lowered, lanes=8)
    M = program.layout.total_words
    list(eng.run_stream(np.zeros((8, M), np.int32), N_ITERS))   # 1 block
    assert eng.stats()["mem_chunks"] == 9 * N_ITERS
    eng.run(np.zeros((3, M), np.int32), 2)                      # 1 block
    stats = eng.stats()
    assert stats["mem_chunks"] == 9 * (N_ITERS + 2)
    assert stats["mem_passes"] == stats["mem_chunks"]   # one chunk each
    assert cache.stats()["mem_chunks"] == stats["mem_chunks"]


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_placement(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself);
    otherwise the cache goes to the fixed ``<repo>/artifacts/jax_cache``.
    Run in a fresh process: the placement is process-wide JAX config."""
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(repo / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax\n"
            "from repro.kernels.cgra_exec.kernel import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    want = (str(tmp_path / env_dir) if env_dir
            else str(repo / "artifacts" / "jax_cache"))
    assert out[0] == out[1] == want
    assert float(out[2]) < 1.0


# ---------------------------------------------------------------------------
# packed blocks: only the declared arrays move between host and device
# ---------------------------------------------------------------------------

def test_live_runs_are_the_declared_arrays():
    """``Program.live``: the arrays' words as ascending runs, adjacent
    arrays merged — one run a bank at the default layout, one run where
    the banks fill up, and the whole scratchpad where the arrays do."""
    from repro.core.dfg import DFGBuilder
    gemm = ual.Program.from_kernel("gemm")
    assert gemm.live == ((0, 64), (2048, 64), (4096, 1))
    assert ual.Program.from_kernel("gemm", bank_words=64).live == ((0, 129),)
    b = DFGBuilder("full")
    for name in "ABCD":
        b.array(name, 16, output=name == "D")
    i = b.counter()
    b.store("D", i, b.load("A", i))
    full = ual.Program.from_builder(b, 4, n_banks=4, bank_words=16)
    assert full.live == ((0, 64),) and full.layout.total_words == 64


def test_pack_batch_is_the_live_columns_of_flatten_batch(compiled):
    """The packed block is the full-width image's live columns, with
    missing arrays zero and short arrays zero-padded; unpacking it gives
    the same dicts as unflattening the image."""
    program, _ = compiled
    rng = np.random.default_rng(19)
    full = program.random_inputs(rng)
    name = program.inputs[0]
    short = dict(full, **{name: full[name][:3]})
    missing = {k: v for k, v in full.items() if k != name}
    mems = [full, short, missing, {}]
    flats = program.flatten_batch(mems)
    block = program.pack_batch(mems)
    cols = np.concatenate([np.arange(lo, lo + n) for lo, n in program.live])
    assert block.shape == (4, len(cols)) and block.dtype == np.int32
    np.testing.assert_array_equal(block, flats[:, cols])
    np.testing.assert_array_equal(program.pack_flats(flats), block)
    for got, want in zip(program.unpack_batch(block),
                         program.unflatten_batch(flats)):
        assert set(got) == set(want) == set(program.arrays)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    with pytest.raises(KeyError, match="unknown array"):
        program.pack_batch([{"nope": np.zeros(4, np.int32)}])


def _same_arrays(program, got, want):
    for name in program.arrays:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("kernel_name,fabric", DEPLOYMENTS)
def test_packed_path_matches_full_width_every_deployment(kernel_name,
                                                         fabric):
    """Every packed entry of the pallas backend — ``execute_batch`` with
    and without ``flats=``, and ``execute_stream`` — gives every declared
    array bit-exact against the full-width ``KernelEngine.run`` of the
    same images and the DFG interpreter, at the default scratchpad
    (M = 8192): three images, padded to a bucket of 8, the stream's last
    chunk ragged."""
    program = ual.Program.from_kernel(kernel_name)
    exe = ual.compile(program, ual.Target.from_name(
        fabric, backend="pallas", **FABRICS[fabric]))
    assert exe.success
    n = program.n_iters
    mems = _mems(program, 3, seed=23)
    flats = program.flatten_batch(mems)
    cache = CompiledKernelCache(buckets=(8,))
    be = ual.backends.PallasBackend(engine=cache)
    eng = cache.engine_for(exe.lowered)
    full, _ = eng.run(flats, n)
    assert full.shape == (3, 8192)
    batch, _ = be.execute_batch(program, None, mems, n, lowered=exe.lowered)
    given, _ = be.execute_batch(program, None, mems, n, lowered=exe.lowered,
                                flats=flats)
    gen = be.execute_stream(program, None, mems, n, chunk=2,
                            lowered=exe.lowered)
    stream = [d for outs, _ in gen for d in outs]
    assert len(stream) == 3
    for m, f, outs in zip(mems, program.unflatten_batch(full),
                          zip(batch, given, stream)):
        want = interpret(program.dfg, m, n)
        _same_arrays(program, f, want)
        for got in outs:
            _same_arrays(program, got, want)
    # one full-width and one packed shape, and the packed path moved
    # only the live words: 2 x L x 8 rows for each of its four blocks
    L = sum(ln for _, ln in program.live)
    assert eng.traces == 2
    assert eng.transfer_words == 2 * 8192 * 8 + 4 * 2 * L * 8


def test_omitted_output_starts_at_zero():
    """An output array that the caller leaves out starts at zero on the
    packed path, and one it passes starts at the passed words: with two
    iterations the fft writes two words of each 16-word output, and the
    other 14 come back as they went in."""
    program = ual.Program.from_kernel("fft", bank_words=64)
    exe = ual.compile(program, ual.Target.from_name(
        "hycube", rows=4, cols=4, backend="pallas"))
    assert exe.success
    rng = np.random.default_rng(29)
    given = program.random_inputs(rng)
    given.update({o: rng.integers(1, 50, program.arrays[o]).astype(np.int32)
                  for o in program.outputs})
    omitted = {k: v for k, v in program.random_inputs(rng).items()
               if k not in program.outputs}
    mems = [given, omitted]
    cache = CompiledKernelCache(buckets=(8,))
    out = ual.backends.PallasBackend(engine=cache).execute_batch(
        program, None, mems, 2, lowered=exe.lowered)[0]
    full = program.unflatten_batch(cache.run(
        exe.lowered, program.flatten_batch(mems), 2)[0])
    for m, got, f in zip(mems, out, full):
        want = interpret(program.dfg, m, 2)
        _same_arrays(program, got, want)
        _same_arrays(program, f, want)
    for o in program.outputs:
        np.testing.assert_array_equal(out[0][o][2:], given[o][2:])
        np.testing.assert_array_equal(out[1][o][2:], 0)


def test_engine_counts_transfer_words(compiled):
    """``transfer_words``: the int32 words each block moves up and back,
    padding rows included — M a row on the full-width entry, L on the
    packed one; ``run`` and ``run_stream`` both count, and the cache
    sums its engines."""
    program, exe = compiled
    M = program.layout.total_words
    L = sum(n for _, n in program.live)
    assert L < M
    cache = CompiledKernelCache()
    eng = cache.engine_for(exe.lowered)
    eng.run(np.zeros((5, M), np.int32), N_ITERS)                # 8 rows
    assert eng.stats()["transfer_words"] == 2 * M * 8
    packed = dict(live=program.live, M=M)
    list(eng.run_stream(np.zeros((20, L), np.int32), N_ITERS, chunk=8,
                        **packed))                              # 3 x 8 rows
    eng.run(np.zeros((1, L), np.int32), N_ITERS, **packed)       # 1 row
    want = 2 * M * 8 + 2 * L * (24 + 1)
    assert eng.stats()["transfer_words"] == want
    assert cache.stats()["transfer_words"] == want
    with pytest.raises(ValueError, match="do not fit"):
        eng.run(np.zeros((1, L + 1), np.int32), N_ITERS, **packed)


def test_sharded_packed_parity_under_forced_two_devices():
    """``pallas_sharded`` takes packed blocks too: in a fresh process
    with two forced host devices, a batch of five (ragged against the
    devices and the ladder) through ``execute_batch`` and
    ``execute_stream`` matches the full-width sharded run and the
    oracle bit for bit, moving L words an image."""
    repo = Path(__file__).resolve().parents[1]
    code = (
        "import numpy as np\n"
        "import jax\n"
        "from repro import ual\n"
        "from repro.core.dfg import interpret\n"
        "from repro.ual.engine import CompiledKernelCache\n"
        "assert len(jax.devices()) == 2\n"
        "program = ual.Program.from_kernel('gemm')\n"
        "exe = ual.compile(program, ual.Target.from_name(\n"
        "    'hycube', rows=4, cols=4, backend='pallas'))\n"
        "rng = np.random.default_rng(0)\n"
        "mems = [program.random_inputs(rng) for _ in range(5)]\n"
        "cache = CompiledKernelCache(buckets=(1, 8))\n"
        "be = ual.backends.PallasBackend(engine=cache, sharded=True)\n"
        "n = program.n_iters\n"
        "batch, info = be.execute_batch(program, None, mems, n,\n"
        "                               lowered=exe.lowered)\n"
        "stream = [d for outs, _ in be.execute_stream(\n"
        "    program, None, mems, n, chunk=16, lowered=exe.lowered)\n"
        "    for d in outs]\n"
        "eng = cache.sharded_engine_for(exe.lowered)\n"
        "words = eng.transfer_words\n"
        "full = program.unflatten_batch(cache.sharded_run(\n"
        "    exe.lowered, program.flatten_batch(mems), n)[0])\n"
        "ok = all(np.array_equal(got[k], interpret(program.dfg, m, n)[k])\n"
        "         for outs in (batch, stream, full)\n"
        "         for m, got in zip(mems, outs) for k in program.arrays)\n"
        "print('DEVICES', info['n_devices'], 'PARITY', ok, 'WORDS', words)\n"
    )
    env = forced_device_env(2)
    env["PYTHONPATH"] = str(repo / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=str(repo), timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    # five images: ceil(5 / 2) = 3 a device, bucket 8, 16 rows a block
    assert f"DEVICES 2 PARITY True WORDS {2 * 2 * 129 * 16}" in out.stdout
