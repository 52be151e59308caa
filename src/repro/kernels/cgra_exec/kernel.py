"""Pallas TPU kernel: execute a linked CGRA configuration over a batch.

TPU adaptation of the paper's execution substrate (DESIGN.md §2).  The
fabric's PE array is small (16–64 PEs) and its cycle loop is sequential,
so a 1:1 port would waste the TPU.  Instead:

  * the BATCH of independent executions (test vectors / workload
    instances) is vectorized across VPU lanes — each lane is one CGRA
    instance, the per-cycle PE update is a (P, lanes) elementwise block;
  * the configuration memory (the paper's CM, 52% of CGRA power because
    it is read every cycle) is the linked table image, resident on-chip
    for the whole kernel — the "CM stays on-chip" analogue: per-PE
    columns in VMEM, the memory PEs' fields as scalars in SMEM;
  * HyCUBE's single-cycle multi-hop routes were resolved at link time
    (``core.lowering``), so operand fetch and register writes are lists
    of addressed row copies out of the stacked ``[O; R]`` PE state, only
    the rows each slot routes — compiler-scheduled routing with zero
    dynamic-routing hardware, exactly the paper's bet;
  * the scratchpad lives in VMEM as an (M, lanes) block; LOAD/STORE are
    data-dependent per lane and become compare/select passes over it
    (TPU has no per-lane gather; this is the idiomatic replacement), run
    only in cycles where the memory PE actually loads or stores.

Grid: (batch_tiles,) — each grid step simulates the whole fabric for one
batch tile.  Cycle ``t = q*II + s``: nested ``fori_loop``s over the round
``q`` and the slot ``s`` carry (O, R); the slot indexes the tables' leading
axis, so no integer division runs per cycle.  Running up to ``II - 1`` cycles past
``LinkedConfig.total_cycles`` changes nothing: no PE fires there, and
only firing stores write the scratchpad.

``n_iters`` is a *traced* scalar (a ``(1, 1)`` int32 operand in SMEM):
the cycle count is a dynamic ``fori_loop`` bound and per-PE firing is
masked on the traced iteration count, so ONE trace of the kernel serves
every iteration count — the property the persistent JIT engine
(``repro.ual.engine``) builds its trace-once/run-many cache on.
``make_cgra_call`` is the shared constructor of the ``pallas_call``; both
the one-shot ``cgra_exec`` wrapper and the engine go through it.

Whether the kernel is compiled (Mosaic) or interpreted is decided by the
platform, in ``interpret_mode``: compiled on a TPU, interpreted elsewhere.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lowering import (KC_HEAD, KC_MOVES, KC_OPS, KC_RES,
                                 KC_WIDTH, KS_CONST, KS_FIELDS, KS_HAS2,
                                 KS_HAS_IDX, KS_LIVE, KS_OPC, KS_Q0, KV_CONST,
                                 KV_LIVE, KV_OP, KV_OPC, KV_Q0, KV_T0OK,
                                 LinkedConfig, kernel_rounds, kernel_tables)
from repro.core.machine import OPC

I32 = jnp.int32

#: rows of the scratchpad one compare/select pass touches at a time
_MEM_CHUNK = 512


def interpret_mode() -> bool:
    """The one place the execution mode is chosen: the Mosaic-compiled
    kernel on a TPU, the Pallas interpreter on every other platform."""
    return jax.default_backend() != "tpu"


#: the persistent compile cache's fixed home inside the checkout
#: (src/repro/kernels/cgra_exec/kernel.py -> <repo>/artifacts/jax_cache);
#: a fixed path, because the path is part of every cache entry's key
JAX_CACHE_DIR = Path(__file__).resolve().parents[4] / "artifacts" / "jax_cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory; otherwise the cache goes to
    ``JAX_CACHE_DIR``.  A kernel compiles in about a second, around JAX's
    default one-second threshold for keeping an entry, so the threshold
    is lowered to keep every kernel.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return jax.config.jax_compilation_cache_dir


def _copies(ctab_ref, s, lst: int, copy):
    """``copy(e)`` for each entry of slot ``s``'s list ``lst`` in the
    row-copy table, ``e`` the entry's first word: a loop as long as the
    list, so the work follows the rows the slot routes."""
    h = s * KC_HEAD + 2 * lst
    at, width = ctab_ref[h + 1], KC_WIDTH[lst]

    def body(i, carry):
        copy(at + i * width)
        return carry
    jax.lax.fori_loop(0, ctab_ref[h], body, 0)


def _lanes(flag, B: int):
    """A scalar flag (SMEM word or scalar predicate) as a (1, B) mask."""
    return jnp.full((1, B), flag.astype(I32)) != 0


def _alu(opc, v0, v1, v2, cvec):
    """Vectorized ALU: all opcodes computed, selected by ``opc`` (P, 1)."""
    sh5 = jnp.bitwise_and(v1, 31)

    def cmp(c):
        return c.astype(I32)
    cases = {
        "ADD": v0 + v1, "SUB": v0 - v1, "MUL": v0 * v1,
        "SHL": jax.lax.shift_left(v0, sh5),
        "SHR": jax.lax.shift_right_arithmetic(v0, sh5),
        "AND": v0 & v1, "OR": v0 | v1, "XOR": v0 ^ v1,
        "MIN": jnp.minimum(v0, v1), "MAX": jnp.maximum(v0, v1),
        "ABS": jnp.where(v0 < 0, -v0, v0),
        "CMPLT": cmp(v0 < v1), "CMPGT": cmp(v0 > v1),
        "CMPEQ": cmp(v0 == v1), "CMPNE": cmp(v0 != v1),
        "CMPLE": cmp(v0 <= v1), "CMPGE": cmp(v0 >= v1),
        "SELECT": jnp.where(v0 != 0, v1, v2),
        "MOVC": cvec,
        "ROUTE": v0,
    }
    out = jnp.zeros_like(v0)
    for name, val in cases.items():
        out = jnp.where(opc == OPC[name], val, out)
    return out


def _mem_passes(mem_ref, chunk: int):
    """``(load, store)`` over the (M, B) scratchpad ref, one ``chunk``-row
    block at a time so a pass costs one block of vector registers."""
    M, B = mem_ref.shape
    n_chunks = M // chunk

    def rows(c):
        start = pl.multiple_of(c * chunk, chunk)
        return pl.ds(start, chunk), \
            jax.lax.broadcasted_iota(I32, (chunk, 1), 0) + start

    def load(addr):                              # addr (1, B) -> (1, B)
        def body(c, acc):
            sl, idx = rows(c)
            return jnp.where(idx == addr, mem_ref[sl, :], acc)
        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((chunk, B), I32))
        return jnp.sum(acc, axis=0, keepdims=True)

    def store(addr, val):                        # (1, B) each
        def body(c, carry):
            sl, idx = rows(c)
            mem_ref[sl, :] = jnp.where(idx == addr, val, mem_ref[sl, :])
            return carry
        jax.lax.fori_loop(0, n_chunks, body, 0)

    return load, store


def _cgra_kernel(niter_ref, stab_ref, vtab_ref, ctab_ref, mem_in_ref,
                 mem_out_ref, st_ref, opnd_ref, reg_ref, *, II: int,
                 n_pes: int, n_regs: int, mem_pes, t_max: int, chunk: int):
    P, R = n_pes, n_regs
    N = P + P * R             # st_ref rows: [O; R] state, then the results
    M, B = mem_out_ref.shape
    n_iters = niter_ref[0, 0]           # traced: one trace, any trip count
    n_rounds = kernel_rounds(n_iters, II, t_max)
    load, store = _mem_passes(mem_out_ref, chunk)

    def copy(c, carry):
        sl = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        mem_out_ref[sl, :] = mem_in_ref[sl, :]
        return carry
    jax.lax.fori_loop(0, M // chunk, copy, 0)

    def fires(live, q0, q):
        it = q - q0
        return (live != 0) & (it >= 0) & (it < n_iters), it

    def cycle(q, s):
        tab = vtab_ref[s]                                   # (P, F)

        def col(f):
            return tab[:, f:f + 1]                          # (P, 1)
        opc = col(KV_OPC)
        fired, it = fires(col(KV_LIVE), col(KV_Q0), q)
        it = jnp.where(col(KV_T0OK) != 0, it, 0)
        cvec = jnp.broadcast_to(col(KV_CONST), (P, B))

        # ---- operand fetch: the routed rows of the previous-cycle state,
        # copied into the (3P, B) operand block; an absent operand reads 0
        opnd_ref[...] = jnp.zeros(opnd_ref.shape, I32)

        def fetch(e):
            opnd_ref[pl.ds(ctab_ref[e], 1), :] = \
                st_ref[pl.ds(ctab_ref[e + 1], 1), :]
        _copies(ctab_ref, s, KC_OPS, fetch)

        def operand(k):
            b = KV_OP + 4 * k
            v = opnd_ref[k * P:(k + 1) * P, :]
            v = jnp.where(col(b) != 0, cvec, v)
            dist = col(b + 1)
            v = jnp.where((dist > 0) & (it < dist),
                          jnp.broadcast_to(col(b + 2), (P, B)), v)
            return jnp.where(col(b + 3) != 0, cvec, v)

        v0, v1, v2 = operand(0), operand(1), operand(2)
        result = _alu(opc, v0, v1, v2, cvec)

        # ---- memory ops: sequential over LSU-capable PEs (port order) ----
        pe_row = jax.lax.broadcasted_iota(I32, (P, 1), 0)
        for j, mp in enumerate(mem_pes):
            base = (s * len(mem_pes) + j) * KS_FIELDS
            m_opc = stab_ref[base + KS_OPC]
            m_const = stab_ref[base + KS_CONST]
            m_fired, _ = fires(stab_ref[base + KS_LIVE],
                               stab_ref[base + KS_Q0], q)
            is_ld = m_fired & (m_opc == OPC["LOAD"])
            is_st = m_fired & (m_opc == OPC["STORE"])
            a0 = v0[mp:mp + 1, :]
            a1 = v1[mp:mp + 1, :]
            has_idx = _lanes(stab_ref[base + KS_HAS_IDX], B)
            has2 = _lanes(stab_ref[base + KS_HAS2], B)
            l_addr = jnp.where(has_idx, a0, 0) + m_const
            lval = jax.lax.cond(is_ld, load, jnp.zeros_like, l_addr)
            s_addr = jnp.where(has2, a0 + m_const, m_const)
            s_val = jnp.where(has2, a1, a0)

            @pl.when(is_st)
            def _():
                store(s_addr, s_val)

            row = jnp.where(_lanes(is_ld, B), lval,
                            jnp.where(_lanes(is_st, B), s_val,
                                      result[mp:mp + 1, :]))
            result = jnp.where(pe_row == mp, row, result)

        # ---- end of cycle: register writes, then output latches -----------
        # the new register file starts as the old one; moves read the old
        # state, result writes the results of PEs that fired
        reg_ref[...] = st_ref[P:N, :]
        st_ref[N:N + P, :] = result

        def move(e):
            reg_ref[pl.ds(ctab_ref[e], 1), :] = \
                st_ref[pl.ds(ctab_ref[e + 1], 1), :]
        _copies(ctab_ref, s, KC_MOVES, move)

        def write(e):
            @pl.when(fires(ctab_ref[e + 2], ctab_ref[e + 3], q)[0])
            def _():
                reg_ref[pl.ds(ctab_ref[e], 1), :] = \
                    st_ref[pl.ds(N + ctab_ref[e + 1], 1), :]
        _copies(ctab_ref, s, KC_RES, write)
        st_ref[0:P, :] = jnp.where(fired, result, st_ref[0:P, :])
        st_ref[P:N, :] = reg_ref[...]

    def round_(q, carry):
        def slot(s, c):
            cycle(q, s)
            return c
        return jax.lax.fori_loop(0, II, slot, carry)

    st_ref[...] = jnp.zeros(st_ref.shape, I32)
    jax.lax.fori_loop(0, n_rounds, round_, 0)


def _mem_chunk(M: int) -> int:
    """Largest power-of-two row block (8 .. ``_MEM_CHUNK``) dividing ``M``;
    ``M`` itself when none does."""
    c = _MEM_CHUNK
    while c >= 8:
        if M % c == 0:
            return c
        c //= 2
    return M


def _vmem_limit_bytes(M: int, bB: int) -> int:
    """Scoped-VMEM budget for one grid step: the in and out scratchpad
    blocks, double-buffered by the pipeline (lanes pad to 128), plus
    headroom for the tables, the loop state and the compiler's scratch.
    At M = 8192 and 128 lanes that is 4 x 4 MiB + 16 MiB = 32 MiB — over
    v5e's 16 MiB default scoped limit, well inside its 128 MiB of VMEM."""
    block = M * max(128, -(-bB // 128) * 128) * 4
    return 4 * block + (16 << 20)


def make_cgra_call(linked: LinkedConfig, *, M: int, bB: int,
                   n_tiles: int = 1):
    """Build the ``pallas_call`` executing ``linked`` over ``n_tiles``
    batch tiles of ``bB`` lanes each.

    Returns a callable ``(niter, stab, vtab, ctab, memT) -> memT'`` where
    ``niter`` is a (1, 1) int32 array (the traced trip count), the tables
    are ``core.lowering.kernel_tables(linked)`` and ``memT`` is the
    (M, n_tiles * bB) transposed scratchpad block.  Everything
    *shape-like* (tile geometry, table dims, the schedule's ``t0_max``) is
    static; the trip count is not — one trace serves every ``n_iters``.

    The platform decides whether the kernel is compiled or interpreted
    (``interpret_mode``); on a TPU the persistent compile cache is placed
    before the first kernel compiles (``use_compile_cache``).
    """
    interpret = interpret_mode()
    if not interpret:
        use_compile_cache()
    kernel = functools.partial(
        _cgra_kernel, II=linked.II, n_pes=linked.n_pes,
        n_regs=linked.n_regs, mem_pes=linked.mem_pes, t_max=linked.t0_max,
        chunk=_mem_chunk(M))
    _, vtab, _ = kernel_tables(linked)
    P, R = linked.n_pes, linked.n_regs
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec(vtab.shape, lambda i: (0, 0, 0)),
            smem,
            pl.BlockSpec((M, bB), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((M, bB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((M, n_tiles * bB), I32),
        # [O; R; results], the operand block, the next register file
        scratch_shapes=[pltpu.VMEM((P * (R + 2), bB), I32),
                        pltpu.VMEM((3 * P, bB), I32),
                        pltpu.VMEM((P * R, bB), I32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(M, bB)),
        interpret=interpret,
        name="cgra_exec",
    )


def cgra_exec(linked: LinkedConfig, mem: jax.Array, n_iters, *,
              lanes: int = 128) -> jax.Array:
    """Execute ``linked`` for ``n_iters`` iterations over mem (B, M) int32.

    Returns the final scratchpad images, (B, M) int32.  One-shot wrapper:
    builds the ``pallas_call`` per invocation — steady-state callers go
    through the persistent JIT engine (``repro.ual.engine``) instead.
    """
    B, M = mem.shape
    bB = min(lanes, max(8, B))
    pad = (-B) % bB
    memT = jnp.pad(mem, ((0, pad), (0, 0))).T.astype(I32)     # (M, B')
    call = make_cgra_call(linked, M=M, bB=bB, n_tiles=(B + pad) // bB)
    tables = [jnp.asarray(t) for t in kernel_tables(linked)]
    out = call(jnp.asarray(n_iters, I32).reshape(1, 1), *tables, memT)
    return out.T[:B]

