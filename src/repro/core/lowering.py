"""Shared lowering: resolve a MachineConfig's static routes to dense tables.

This is the single source of truth for the **lowered artifact** every
execution engine consumes.  HyCUBE's central claim is that the
interconnect is *compiler-scheduled*: crossbar settings are static per
II-slot, so a single-cycle multi-hop path is a fixed combinational chain.
We exploit exactly that property — every wire chain is resolved ONCE, at
lowering time, into a direct (source PE, source register) select, so no
engine ever routes dynamically:

  * the vectorized batched simulator (``core.simulator.simulate_batch``)
    turns operand fetch into static numpy gathers over the PE-output /
    register state,
  * the Pallas ``cgra_exec`` TPU kernel turns it into per-slot lists of
    addressed row copies out of the same state, only the rows the slot
    routes (the TPU-native analogue of the clockless-repeater bypass).

The ``ual`` compile pipeline runs this as its ``lowering`` pass and
memoizes the result in the mapping cache next to the ``MapResult``,
keyed by the same ``(program.digest, target.digest)`` pair — lower once,
run many.

Lowered operand/source kinds (values in the dense tables):
  K_NONE   = 0 — absent operand
  K_O      = 1 — previous-cycle output latch of PE ``pe``
  K_R      = 2 — register ``reg`` of PE ``pe`` (previous-cycle value)
  K_CONST  = 3 — the instruction immediate
  K_RESULT = 4 — current-cycle ALU result of own PE (register writes only)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro.core.dfg import DFG, DataLayout
from repro.core.machine import (OPC, MachineConfig, SRC_CONST, SRC_NONE,
                                SRC_REG, SRC_SELF, XB_IN, XB_NONE, XB_O,
                                XB_REG)

K_NONE, K_O, K_R, K_CONST, K_RESULT = 0, 1, 2, 3, 4

#: bump when the dense-table layout changes — folded into the on-disk
#: cache entry name so stale lowered artifacts are never deserialized
#: (v2: added the ``unresolved_inputs`` lowering-health counter;
#: v3: added the memory slots' scratchpad row ranges, ``mem_rows``)
LOWERING_VERSION = 3

#: the end of a row range that no layout bounds: past any scratchpad
MEM_ROWS_END = int(np.iinfo(np.int32).max)

#: rows of the scratchpad one compare/select pass of ``cgra_exec``
#: touches at a time
MEM_CHUNK = 512


@dataclass
class LinkedConfig:
    """Dense int32 tables driving every execution engine (CM-in-VMEM image
    for the Pallas kernel, gather/scatter plans for the batched simulator).
    """
    II: int
    n_pes: int
    n_regs: int
    mem_pes: Tuple[int, ...]
    scalar: np.ndarray    # (S, P, 4)    [opcode, const, use_const, t0]
    ops: np.ndarray       # (S, P, 3, 5) [kind, pe, reg, dist, init]
    regw: np.ndarray      # (S, P, R, 3) [kind, pe, reg]
    #: the fabric's shared-scratchpad port budget, threaded through
    #: unconditionally by ``link_config``.  0 means *unknown/unbounded*:
    #: the engines' runtime oversubscription guard (``limit and
    #: ports_used > limit``) and the static verifier's UAL001 check are
    #: both disabled — port pressure is still *recorded* in ``SimStats``.
    #: Every registered fabric sets a real limit; 0 only appears on
    #: hand-built tables that never saw a fabric.
    n_mem_ports: int = 0
    #: how many wire selects (``SRC_IN`` operands / ``XB_IN`` register
    #: writes) failed to resolve to a driver at lowering time and were
    #: collapsed to a silent ``K_NONE`` row.  0 for every config a
    #: correct mapper emits; the static verifier
    #: (``repro.analysis.verifier``, code UAL004) flags any nonzero
    #: count without re-deriving routing — this is the root exposure of
    #: the silent-``K_NONE`` lowering hazard
    unresolved_inputs: int = 0
    #: (S, n_mem, 2) int32 ``[lo, hi)``: the scratchpad rows the LOAD or
    #: STORE in each (slot, memory PE) may touch — its array's words under
    #: the program's layout.  ``[0, MEM_ROWS_END)`` where no layout was at
    #: hand (the default): the whole scratchpad
    mem_rows: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.mem_rows is None:
            self.mem_rows = full_mem_rows(self.II, len(self.mem_pes))

    def cm_bytes(self) -> int:
        return self.scalar.nbytes + self.ops.nbytes + self.regw.nbytes

    def __getstate__(self):
        # runtime attachments (the memoized batched-engine plans) must not
        # leak into cache pickles — only the dense tables are the artifact
        return {k: v for k, v in self.__dict__.items()
                if not k.startswith("_")}

    @property
    def t0_max(self) -> int:
        """Latest issue slot in the schedule (static: a table property)."""
        t0 = self.scalar[:, :, 3]
        return int(t0.max()) if (t0 >= 0).any() else 0

    def total_cycles(self, n_iters: int) -> int:
        return self.t0_max + n_iters * self.II + self.II + 2

    def mem_slot_mask(self) -> np.ndarray:
        """(S, n_mem) bool: the (slot, memory PE) pairs holding a scheduled
        LOAD or STORE.  Each fires once per iteration, and each firing is
        one pass of ``cgra_exec`` over its ``mem_rows``."""
        sc = self.scalar[:, list(self.mem_pes)]
        opc, t0 = sc[..., 0], sc[..., 3]
        return ((opc == OPC["LOAD"]) | (opc == OPC["STORE"])) & (t0 >= 0)

    @property
    def mem_slots(self) -> int:
        """Scheduled LOAD/STORE slots (``mem_slot_mask``)."""
        return int(self.mem_slot_mask().sum())


def full_mem_rows(S: int, n_mem: int) -> np.ndarray:
    """Row ranges that bound nothing: ``[0, MEM_ROWS_END)`` everywhere."""
    rows = np.zeros((S, n_mem, 2), np.int32)
    rows[..., 1] = MEM_ROWS_END
    return rows


def _mem_row_ranges(cfg: MachineConfig, dfg: DFG, layout: DataLayout
                   ) -> np.ndarray:
    """``[lo, hi)`` rows of each (slot, memory PE): the words of the array
    its LOAD or STORE names, ``[base, base + length)`` under ``layout``;
    the whole scratchpad for a slot with no such access.  ``dfg`` is the
    mapped DFG (``cfg.node_id`` indexes its nodes)."""
    mp = list(cfg.fabric.mem_pes)
    rows = full_mem_rows(cfg.II, len(mp))
    for s in range(cfg.II):
        for j, p in enumerate(mp):
            nid = int(cfg.node_id[s, p])
            node = dfg.nodes[nid] if nid >= 0 else None
            if (node is not None and node.is_mem
                    and node.array in layout.bases):
                lo = layout.bases[node.array]
                rows[s, j] = lo, lo + dfg.arrays[node.array]
    return rows


def mem_chunk_rows(M: int) -> int:
    """Rows of one pass step over an ``M``-row scratchpad: the largest
    power of two (8 .. ``MEM_CHUNK``) dividing ``M``; ``M`` itself when
    none does."""
    c = MEM_CHUNK
    while c >= 8:
        if M % c == 0:
            return c
        c //= 2
    return M


def chunk_span(lo, hi, M: int, minimum=np.minimum):
    """The chunks ``[c0, c1)`` of ``mem_chunk_rows(M)`` rows that cover
    rows ``[lo, hi)``, clamped to the block's ``M // chunk`` chunks.

    Shifts by the power-of-two chunk, no division, so the kernel calls it
    on SMEM scalars (with ``jnp.minimum``) and the engine's counter on
    numpy arrays.  A chunk that is no power of two is the whole block,
    its one chunk."""
    chunk = mem_chunk_rows(M)
    n = M // chunk
    if chunk & (chunk - 1):
        return 0, n
    k = chunk.bit_length() - 1
    return minimum(lo >> k, n), minimum(((hi - 1) >> k) + 1, n)


def kernel_rounds(n_iters, II: int, t0_max: int):
    """Rounds of the II slots ``cgra_exec`` runs for ``n_iters``:
    ``ceil(total_cycles / II)``, where ``n_iters`` may be a traced scalar."""
    return n_iters + 1 + (t0_max + 2 + II - 1) // II


# Field layout of the Pallas kernel's tables (``kernel_tables``).
# Per-PE vector table, one (P, KV_FIELDS) row block per slot:
KV_OPC, KV_CONST, KV_T0OK, KV_LIVE, KV_Q0 = 0, 1, 2, 3, 4
#: operand k occupies fields KV_OP + 4k .. KV_OP + 4k + 3:
#: [is-immediate, dist, init, takes-trailing-immediate]
KV_OP = 5
KV_FIELDS = KV_OP + 12
# Scalar (SMEM) table, KS_FIELDS words per (slot, memory PE); KS_LO and
# KS_HI are the ``mem_rows`` range ``[lo, hi)`` its pass is bounded to
KS_OPC, KS_CONST, KS_LIVE, KS_Q0, KS_HAS_IDX, KS_HAS2 = 0, 1, 2, 3, 4, 5
KS_LO, KS_HI = 6, 7
KS_FIELDS = 8
# Row-copy (SMEM) table: a KC_HEAD-word header per slot, [count, offset]
# of each of its three lists, then the lists' entries.
#: the lists: operand copies, register moves, result writes
KC_OPS, KC_MOVES, KC_RES = 0, 1, 2
KC_HEAD = 6
#: words per entry: operand copy [operand-block row k*P + pe, source row
#: in [O; R]]; register move [register row, source row in [O; R]];
#: result write [register row, source PE, its live flag, its q0]
KC_WIDTH = (2, 2, 4)


def kernel_tables(linked: LinkedConfig
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The dense tables re-laid out for the Pallas kernel: ``(stab, vtab,
    ctab)``.

    The kernel keeps every table 2-D per slot with a wide minor axis (the
    TPU's (8, 128) tiling refuses the raw tables' minor dims of 3 and 5),
    reads memory-PE fields and row addresses as scalars, and does no
    vector integer division.  So everything static is folded here, once:

      * ``vtab`` (S, P, KV_FIELDS): per-PE columns.  ``q0`` makes the
        iteration index a subtraction: with ``t = q*II + s``,
        ``(t - t0) // II == q - q0``.
      * ``stab`` (S * n_mem * KS_FIELDS,) int32: the memory PEs' fields,
        flat for scalar memory, their row ranges (``mem_rows``) among
        them.
      * ``ctab`` (flat int32, scalar memory): the rows each slot routes,
        as lists of row copies (``KC_*``).  Source rows index the stacked
        ``[O; R]`` state (``pe`` for K_O, ``P + pe*R + reg`` for K_R); an
        operand with no such source is absent and reads 0; a register
        row with no move or result write keeps its value.  A result
        write carries its source PE's ``live``/``q0``, so whether it
        fires is a scalar test.

    Memoized on the instance (underscore attribute: never pickled).
    """
    cached = getattr(linked, "_kernel_tables", None)
    if cached is not None:
        return cached
    S, P, R = linked.II, linked.n_pes, linked.n_regs
    sc, ops = linked.scalar, linked.ops
    opc, const, use_c, t0 = (sc[..., i] for i in range(4))
    t0ok = t0 >= 0
    live = (opc != OPC["NOP"]) & t0ok
    slot = np.arange(S)[:, None]
    t0c = np.where(t0ok, t0, 0)
    q0 = np.where(t0ok, t0c // S + (slot < t0c % S), 0)

    def src(kind, pe, reg):
        return np.where(kind == K_O, pe,
                        np.where(kind == K_R, P + pe * R + reg, -1))

    vtab = np.zeros((S, P, KV_FIELDS), np.int32)
    vtab[..., KV_OPC] = opc
    vtab[..., KV_CONST] = const
    vtab[..., KV_T0OK] = t0ok
    vtab[..., KV_LIVE] = live
    vtab[..., KV_Q0] = q0
    kinds = ops[..., 0]
    n_ops = (kinds != K_NONE).sum(axis=-1)
    for k in range(3):
        kind, dist, init = ops[:, :, k, 0], ops[:, :, k, 3], ops[:, :, k, 4]
        b = KV_OP + 4 * k
        vtab[..., b] = kind == K_CONST
        vtab[..., b + 1] = dist
        vtab[..., b + 2] = init
        # the immediate is a *trailing* ALU operand when use_const is set
        vtab[..., b + 3] = (kind == K_NONE) & (use_c != 0) & (n_ops == k)

    mp = list(linked.mem_pes)
    stab = np.zeros((S, len(mp), KS_FIELDS), np.int32)
    if mp:
        stab[..., KS_OPC] = opc[:, mp]
        stab[..., KS_CONST] = const[:, mp]
        stab[..., KS_LIVE] = live[:, mp]
        stab[..., KS_Q0] = q0[:, mp]
        stab[..., KS_HAS_IDX] = ops[:, mp, 0, 0] != K_NONE
        stab[..., KS_HAS2] = ops[:, mp, 1, 0] != K_NONE
        stab[..., KS_LO] = linked.mem_rows[..., 0]
        stab[..., KS_HI] = linked.mem_rows[..., 1]
    # scalar memory cannot hold an empty array
    stab = stab.reshape(-1) if stab.size else np.zeros(1, np.int32)

    # operand-block row k*P + pe, and register row p*R + r, of each source
    op_src = np.stack([src(*(ops[:, :, k, i] for i in range(3)))
                       for k in range(3)], axis=1).reshape(S, 3 * P)
    rk, rp, rr = (linked.regw[..., i].reshape(S, P * R) for i in range(3))
    move_src = src(rk, rp, rr)
    head = np.zeros((S, 3, 2), np.int64)
    body, at = [], S * KC_HEAD
    for s in range(S):
        opd, mvd, rsd = (np.flatnonzero(op_src[s] >= 0),
                         np.flatnonzero(move_src[s] >= 0),
                         np.flatnonzero(rk[s] == K_RESULT))
        pe = rp[s, rsd]
        lists = (np.stack([opd, op_src[s, opd]], axis=1),
                 np.stack([mvd, move_src[s, mvd]], axis=1),
                 np.stack([rsd, pe, live[s, pe], q0[s, pe]], axis=1))
        for j, entries in enumerate(lists):
            head[s, j] = len(entries), at
            body.append(entries.reshape(-1))
            at += entries.size
    ctab = np.concatenate([head.reshape(-1)] + body).astype(np.int32)
    linked._kernel_tables = (stab, vtab, ctab)
    return linked._kernel_tables


def state_copy_counts(linked: LinkedConfig) -> Tuple[int, int]:
    """``(copied, dense)`` PE-state rows per round of the II slots: the
    rows the kernel's copy lists move (``ctab``), and the rows a dense
    one-hot scan steps through — each of the three operands and the
    register moves over all ``N = P + P*R`` state rows, the result writes
    over the ``P`` results."""
    ctab = kernel_tables(linked)[2]
    S, P, R = linked.II, linked.n_pes, linked.n_regs
    head = ctab[:S * KC_HEAD].reshape(S, 3, 2)
    n = P + P * R
    return int(head[..., 0].sum()), S * (4 * n + P)


def mem_chunk_counts(linked: LinkedConfig, M: int) -> Tuple[int, int]:
    """``(bounded, dense)`` chunk steps of the scratchpad passes per
    iteration over an ``M``-row block: each scheduled LOAD/STORE slot's
    chunks that cover its ``mem_rows`` (``chunk_span``, as the kernel
    computes them), and the ``M // chunk`` chunks of an unbounded pass."""
    mem = linked.mem_slot_mask()
    c0, c1 = chunk_span(linked.mem_rows[..., 0], linked.mem_rows[..., 1], M)
    bounded = int(np.where(mem, c1 - c0, 0).sum())
    return bounded, int(mem.sum()) * (M // mem_chunk_rows(M))


def lowered_fingerprint(linked: LinkedConfig) -> str:
    """Content hash of the dense tables themselves.

    Identifies a lowered artifact independently of how it was produced —
    the persistent JIT execution engine (``ual.engine``) keys its trace
    cache on it, so two Executables sharing one artifact (same mapping,
    different Program wrappers) also share every compiled trace.  Memoized
    on the instance (underscore attribute: excluded from cache pickles by
    ``LinkedConfig.__getstate__``).
    """
    fp = getattr(linked, "_fingerprint", None)
    if fp is None:
        import hashlib
        h = hashlib.sha256()
        h.update(f"{LOWERING_VERSION}:{linked.II}:{linked.n_pes}:"
                 f"{linked.n_regs}:{linked.mem_pes}:"
                 f"{linked.n_mem_ports}".encode())
        for a in (linked.scalar, linked.ops, linked.regw, linked.mem_rows):
            h.update(np.ascontiguousarray(a).tobytes())
        fp = h.hexdigest()
        linked._fingerprint = fp
    return fp


def config_fingerprint(cfg: MachineConfig) -> str:
    """Content hash of the executable configuration state.

    Identifies WHICH configuration a lowered artifact was derived from:
    the wall-clock-budgeted mapper may legitimately produce different
    configs for the same ``(program, target)`` key on different machines,
    so cached lowered tables are only trusted when their fingerprint
    matches the config in use.
    """
    import hashlib
    h = hashlib.sha256()
    h.update(str(cfg.II).encode())
    for a in (cfg.opcode, cfg.const, cfg.use_const, cfg.t0, cfg.op_src,
              cfg.xbar, cfg.regw):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _resolve_drivers(cfg: MachineConfig, s: int) -> np.ndarray:
    """Per-link ultimate driver for slot ``s``: rows [kind, pe, reg].

    Relaxes the bypass chain the same way the reference simulator does per
    cycle — but once, at lowering time, because the chain is static.
    """
    f = cfg.fabric
    n_links = len(f.links)
    drv = np.zeros((n_links, 3), np.int64)          # K_NONE
    for _ in range(max(1, f.max_hops)):
        changed = False
        for p in range(f.n_pes):
            for j, li in enumerate(f.out_links(p)):
                kind, idx = cfg.xbar[s, p, j]
                if kind == XB_NONE or drv[li, 0] != K_NONE:
                    continue
                if kind == XB_O:
                    drv[li] = (K_O, p, 0)
                    changed = True
                elif kind == XB_REG:
                    drv[li] = (K_R, p, idx)
                    changed = True
                elif kind == XB_IN and drv[idx, 0] != K_NONE:
                    drv[li] = drv[idx]
                    changed = True
        if not changed:
            break
    return drv


def link_config(cfg: MachineConfig, dfg: Optional[DFG] = None,
                layout: Optional[DataLayout] = None) -> LinkedConfig:
    """Lower a MachineConfig to the dense tables the engines execute.

    Given the mapped ``dfg`` and its ``layout``, each memory slot's
    scratchpad pass is bounded to its array's rows (``_mem_row_ranges``);
    without them it spans the whole scratchpad."""
    S, P = cfg.II, cfg.fabric.n_pes
    R = cfg.regw.shape[2]
    scalar = np.zeros((S, P, 4), np.int32)
    ops = np.zeros((S, P, 3, 5), np.int32)
    regw = np.zeros((S, P, R, 3), np.int32)
    scalar[:, :, 0] = cfg.opcode
    scalar[:, :, 1] = cfg.const
    scalar[:, :, 2] = cfg.use_const
    scalar[:, :, 3] = cfg.t0

    unresolved = 0
    for s in range(S):
        drv = _resolve_drivers(cfg, s)
        for p in range(P):
            for k in range(3):
                kind, idx, dist, init = cfg.op_src[s, p, k]
                if kind == SRC_NONE:
                    row = (K_NONE, 0, 0, dist, init)
                elif kind == SRC_REG:
                    row = (K_R, p, idx, dist, init)
                elif kind == SRC_SELF:
                    row = (K_O, p, 0, dist, init)
                elif kind == SRC_CONST:
                    row = (K_CONST, 0, 0, dist, init)
                else:                                  # SRC_IN: wire -> driver
                    dk, dp, dr = drv[idx]
                    if dk == K_NONE:
                        # the driver fixed point never resolved: the
                        # operand collapses to an absent source.  Count
                        # it so the verifier / fingerprint consumers can
                        # flag the hazard without re-deriving routing
                        unresolved += 1
                    row = (int(dk), int(dp), int(dr), dist, init)
                ops[s, p, k] = row
            for r in range(R):
                kind, idx = cfg.regw[s, p, r]
                if kind == XB_NONE:
                    regw[s, p, r] = (K_NONE, 0, 0)
                elif kind == XB_O:
                    regw[s, p, r] = (K_RESULT, p, 0)
                else:                                  # XB_IN via wire
                    dk, dp, dr = drv[idx]
                    if dk == K_NONE:
                        unresolved += 1
                    regw[s, p, r] = (int(dk), int(dp), int(dr))
    return LinkedConfig(II=cfg.II, n_pes=P, n_regs=R,
                        mem_pes=tuple(cfg.fabric.mem_pes),
                        scalar=scalar, ops=ops, regw=regw,
                        n_mem_ports=cfg.fabric.n_mem_ports,
                        unresolved_inputs=unresolved,
                        mem_rows=(None if dfg is None or layout is None
                                  else _mem_row_ranges(cfg, dfg, layout)))
