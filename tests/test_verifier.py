"""Compile-time config verifier: differential corruption fuzzing.

Strategy: compile real kernels to real configs (which must verify
CLEAN on every published deployment), then inject one corruption
class at a time into a cloned config and assert the verifier reports
exactly the expected diagnostic code.  The injections mirror the hazard
classes the engines would otherwise only hit at runtime — or never
(silent-``K_NONE`` wire collapses).
"""
from dataclasses import replace

import numpy as np
import pytest

from deployments import DEPLOYMENTS, FABRICS
from repro import ual
from repro.analysis.verifier import (CODES, CheckReport, Diagnostic,
                                     VerifyError, raise_if_errors, verify)
from repro.core.adl import Fabric
from repro.core.lowering import K_NONE, link_config
from repro.core.machine import (OPC, SRC_IN, SRC_REG, XB_IN, XB_NONE, XB_O,
                                MachineConfig)
from repro.core.simulator import BatchedSimulator



def _compiled(kernel, fab_name, kwargs):
    target = ual.Target.from_name(fab_name, **kwargs)
    program = ual.Program.from_kernel(
        kernel, n_banks=max(1, target.fabric.n_mem_ports))
    exe = ual.compile(program, target)
    assert exe.success, f"{kernel} must map onto {fab_name}"
    return program, target, exe


def _clone(cfg: MachineConfig, fabric: Fabric = None) -> MachineConfig:
    return replace(cfg, fabric=fabric if fabric is not None else cfg.fabric,
                   opcode=cfg.opcode.copy(), const=cfg.const.copy(),
                   use_const=cfg.use_const.copy(), t0=cfg.t0.copy(),
                   node_id=cfg.node_id.copy(), op_src=cfg.op_src.copy(),
                   xbar=cfg.xbar.copy(), regw=cfg.regw.copy())


def _firing_locus(cfg):
    """First (slot, pe) holding a non-NOP instruction."""
    for s in range(cfg.II):
        for p in range(cfg.fabric.n_pes):
            if cfg.opcode[s, p] != OPC["NOP"]:
                return s, p
    raise AssertionError("config has no instructions")


@pytest.fixture(scope="module")
def gemm_hycube():
    return _compiled("gemm", "hycube", dict(rows=4, cols=4))


# -- clean configs: zero findings on every published deployment -------------

@pytest.mark.parametrize("kernel,fab_name", DEPLOYMENTS)
def test_clean_configs_verify_clean(kernel, fab_name):
    program, target, exe = _compiled(kernel, fab_name, FABRICS[fab_name])
    rep = verify(cfg=exe.map_result.config, linked=exe.lowered,
                 program=program)
    assert rep.diagnostics == [], rep.render()
    assert rep.ok and rep.counts() == {"errors": 0, "warnings": 0,
                                       "infos": 0}
    # the pipeline already verified: the Executable carries the report
    assert exe.check_report is not None and exe.check_report.ok


# -- corruption injections: each class -> its expected code -----------------

def test_port_oversubscription_ual001(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    f1 = Fabric.from_json(cfg.fabric.to_json())
    f1.n_mem_ports = 1          # gemm needs >1 port somewhere in the II
    rep = verify(cfg=_clone(cfg, f1), program=program)
    assert "UAL001" in rep.codes() and not rep.ok
    d = next(d for d in rep.diagnostics if d.code == "UAL001")
    assert d.slot is not None and "port" in d.message


def test_dangling_wire_ual004(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    f = cfg.fabric
    bad = _clone(cfg)
    for s in range(cfg.II):
        driven = {li for p in range(f.n_pes)
                  for j, li in enumerate(f.out_links(p))
                  if bad.xbar[s, p, j, 0] != XB_NONE}
        undriven = [li for li in range(len(f.links)) if li not in driven]
        firing = [p for p in range(f.n_pes)
                  if bad.opcode[s, p] != OPC["NOP"]]
        if undriven and firing:
            bad.op_src[s, firing[0], 0] = (SRC_IN, undriven[0], 0, 0)
            break
    else:
        pytest.skip("no slot with an undriven link and a firing PE")
    rep = verify(cfg=bad, program=program)
    assert "UAL004" in rep.codes() and not rep.ok
    # differential: lowering collapses the same select to a silent K_NONE
    # and counts it — exactly the bug class the verifier makes loud
    linked = link_config(bad)
    assert linked.unresolved_inputs >= 1
    rep2 = verify(linked=linked, program=program)   # tables-only fallback
    assert "UAL004" in rep2.codes() and not rep2.ok


def test_hop_budget_excess_ual005(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    f = cfg.fabric
    bad = _clone(cfg)
    links = [tuple(l_pair) for l_pair in f.links]
    path = [0, 1, 2, 3, 7, 11, 15]       # 6 hops > hycube's max_hops=4
    assert f.max_hops < len(path) - 1
    s, prev_li = 0, None
    for a, b in zip(path, path[1:]):
        li = links.index((a, b))
        j = f.out_links(a).index(li)
        bad.xbar[s, a, j] = (XB_O, 0) if prev_li is None else (XB_IN,
                                                              prev_li)
        prev_li = li
    p = next(p for p in range(f.n_pes) if bad.opcode[s, p] != OPC["NOP"])
    bad.op_src[s, p, 0] = (SRC_IN, prev_li, 0, 0)
    rep = verify(cfg=bad, program=program)
    assert "UAL005" in rep.codes() and not rep.ok
    d = next(d for d in rep.diagnostics if d.code == "UAL005")
    assert f"{len(path) - 1}-hop" in d.message


def test_out_of_range_reg_ual008(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    s, p = _firing_locus(bad)
    bad.op_src[s, p, 0] = (SRC_REG, bad.regw.shape[2] + 2, 0, 0)
    rep = verify(cfg=bad, program=program)
    assert "UAL008" in rep.codes() and not rep.ok


def test_schedule_inconsistency_ual009(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    s, p = _firing_locus(bad)
    bad.t0[s, p] = int(bad.t0[s, p]) + 1      # t0 % II no longer == slot
    rep = verify(cfg=bad, program=program)
    assert "UAL009" in rep.codes() and not rep.ok


def test_write_write_race_ual002_and_overlap_ual003(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    mem_pes = sorted(set(link_config(cfg).mem_pes))
    assert len(mem_pes) >= 2
    s = 1
    for p in mem_pes[:2]:      # two const-addr STOREs, same slot+address
        bad.opcode[s, p] = OPC["STORE"]
        bad.const[s, p] = 3
        bad.use_const[s, p] = 1
        bad.t0[s, p] = s
        bad.op_src[s, p, :] = 0
    rep = verify(cfg=bad, program=program)
    assert "UAL002" in rep.codes() and not rep.ok
    # turn one writer into a reader: write-write becomes load/store overlap
    overlap = _clone(bad)
    overlap.opcode[s, mem_pes[0]] = OPC["LOAD"]
    rep2 = verify(cfg=overlap, program=program)
    assert "UAL002" not in rep2.codes()
    assert "UAL003" in rep2.codes()


def test_const_addr_out_of_bounds_ual012(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    p = sorted(set(link_config(cfg).mem_pes))[0]
    s = 1
    bad.opcode[s, p] = OPC["STORE"]
    bad.const[s, p] = program.layout.total_words + 100
    bad.use_const[s, p] = 1
    bad.t0[s, p] = s
    bad.op_src[s, p, :] = 0
    rep = verify(cfg=bad, program=program)
    assert "UAL012" in rep.codes() and not rep.ok
    # without a program (no layout), bounds are unknowable: no UAL012
    assert "UAL012" not in verify(cfg=bad).codes()


def test_mem_op_on_non_mem_pe_ual010(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    non_mem = sorted(set(range(cfg.fabric.n_pes))
                     - set(link_config(cfg).mem_pes))
    if not non_mem:
        pytest.skip("every PE on this fabric has scratchpad access")
    bad = _clone(cfg)
    s, p = 0, non_mem[0]
    bad.opcode[s, p] = OPC["LOAD"]
    bad.const[s, p] = 0
    bad.use_const[s, p] = 1
    bad.t0[s, p] = s
    bad.op_src[s, p, :] = 0
    rep = verify(cfg=bad, program=program)
    assert "UAL010" in rep.codes() and not rep.ok


def test_dead_code_warning_ual007(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    for s in range(cfg.II):
        idle = [p for p in range(cfg.fabric.n_pes)
                if bad.opcode[s, p] == OPC["NOP"]]
        if idle:
            p = idle[0]
            bad.opcode[s, p] = OPC["MOVC"]     # result feeds nothing
            bad.const[s, p] = 7
            bad.use_const[s, p] = 1
            bad.t0[s, p] = s
            bad.op_src[s, p, :] = 0
            break
    else:
        pytest.skip("fully utilized config, nowhere to hide dead code")
    rep = verify(cfg=bad, program=program)
    assert rep.ok                              # warnings don't fail verify
    assert "UAL007" in rep.codes()
    assert rep.counts()["warnings"] >= 1


def test_use_before_def_warning_ual006(gemm_hycube):
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    bad = _clone(cfg)
    s, p = _firing_locus(bad)
    # read a register no slot ever writes on this PE
    linked = link_config(bad)
    unwritten = [r for r in range(linked.n_regs)
                 if not any(linked.regw[t, p, r, 0] != K_NONE
                            for t in range(linked.II))]
    if not unwritten:
        pytest.skip("every register of this PE is written somewhere")
    bad.op_src[s, p, 0] = (SRC_REG, unwritten[0], 0, 0)
    rep = verify(cfg=bad, program=program)
    assert "UAL006" in rep.codes()


# -- report / registry mechanics -------------------------------------------

def test_code_registry_is_stable():
    assert set(CODES) == {f"UAL{i:03d}" for i in range(1, 13)}
    for code, (severity, meaning) in CODES.items():
        assert severity in ("error", "warning", "info")
        assert meaning


def test_report_rendering_and_json():
    rep = CheckReport(name="k @ f", diagnostics=[
        Diagnostic("UAL001", "error", "too many", slot=2),
        Diagnostic("UAL007", "warning", "dead", slot=0, pe=3)])
    text = rep.render()
    assert "verify k @ f:" in text and "UAL001" in text
    assert "[slot 0/pe 3]" in text
    j = rep.to_json()
    assert j["ok"] is False and j["codes"] == ["UAL001", "UAL007"]
    assert j["diagnostics"][0]["slot"] == 2
    with pytest.raises(VerifyError) as ei:
        raise_if_errors(rep)
    assert ei.value.report is rep and "UAL001" in str(ei.value)
    clean = CheckReport(name="x")
    assert raise_if_errors(clean) is clean
    assert clean.summary() == "clean (0 findings)"


def test_verify_requires_an_input():
    with pytest.raises(ValueError):
        verify()


# -- pipeline / service integration ----------------------------------------

def test_compile_rejects_corrupted_config(gemm_hycube):
    """Acceptance: a deliberately corrupted cached config fails
    ``ual.compile()`` with a rendered UAL*** diagnostic."""
    program, target, exe = gemm_hycube
    bad = _clone(exe.map_result.config)
    s, p = _firing_locus(bad)
    bad.op_src[s, p, 0] = (SRC_REG, bad.regw.shape[2] + 2, 0, 0)
    cache = ual.MappingCache(disk_dir=None)
    cache.put((program.digest, target.digest),
              replace(exe.map_result, config=bad))
    with pytest.raises(VerifyError) as ei:
        ual.compile(program, target, cache=cache)
    assert "UAL008" in str(ei.value)
    assert not ei.value.report.ok
    # collect mode: same corrupt config, no raise, report on the exe
    loose = ual.compile(program, target, cache=cache,
                        pipeline=ual.default_pipeline(strict_verify=False))
    assert loose.check_report is not None
    assert "UAL008" in loose.check_report.codes()
    # the verify pass is on the pass record either way
    assert any(p.name == "verify" for p in loose.compile_info.passes)


def test_warning_only_config_still_compiles_and_runs(gemm_hycube):
    """Acceptance: warning-only findings produce a runnable Executable
    carrying the report — they never abort the compile."""
    program, target, exe = gemm_hycube
    warn = _clone(exe.map_result.config)
    for s in range(warn.II):
        idle = [p for p in range(warn.fabric.n_pes)
                if warn.opcode[s, p] == OPC["NOP"]]
        if idle:
            p = idle[0]
            warn.opcode[s, p] = OPC["MOVC"]
            warn.const[s, p] = 7
            warn.use_const[s, p] = 1
            warn.t0[s, p] = s
            warn.op_src[s, p, :] = 0
            break
    cache = ual.MappingCache(disk_dir=None)
    cache.put((program.digest, target.digest),
              replace(exe.map_result, config=warn))
    exe2 = ual.compile(program, target, cache=cache)   # strict: no raise
    rep = exe2.check_report
    assert rep is not None and rep.ok and rep.counts()["warnings"] >= 1
    out = exe2.run(**program.random_inputs(np.random.default_rng(0)))
    assert set(out) == set(program.arrays)


def test_service_rejects_verifier_error(gemm_hycube):
    program, target, exe = gemm_hycube
    bad = _clone(exe.map_result.config)
    s, p = _firing_locus(bad)
    bad.op_src[s, p, 0] = (SRC_REG, bad.regw.shape[2] + 2, 0, 0)
    cache = ual.MappingCache(disk_dir=None)
    cache.put((program.digest, target.digest),
              replace(exe.map_result, config=bad))
    svc = ual.Service(max_batch=4, max_wait_ms=1.0, cache=cache)
    try:
        fut = svc.submit(program, target,
                         **program.random_inputs(np.random.default_rng(0)))
        with pytest.raises(ual.ServiceRejected) as ei:
            fut.result(timeout=60)
        assert ei.value.reason == "verifier-error"
        assert "UAL008" in str(ei.value)
        assert svc.stats()["rejects"].get("verifier-error") == 1
    finally:
        svc.shutdown()


# -- satellite: n_mem_ports threading + the limit-0 guard semantics ---------

def test_linked_config_threads_fabric_port_limit(gemm_hycube):
    program, target, exe = gemm_hycube
    assert exe.lowered.n_mem_ports == target.fabric.n_mem_ports
    assert target.fabric.n_mem_ports > 0
    assert exe.lowered.unresolved_inputs == 0


def test_port_limit_zero_disables_guard_but_records_pressure(gemm_hycube):
    """``n_mem_ports == 0`` means unknown/unbounded: the batched engine's
    runtime guard must not raise, pressure is still in the stats, and the
    verifier says so (UAL011 info)."""
    program, _, exe = gemm_hycube
    cfg = exe.map_result.config
    linked = link_config(cfg)
    # static steady-state pressure from the tables
    static_peak = max(
        sum(1 for p in range(linked.n_pes)
            if int(linked.scalar[s, p, 0]) in (OPC["LOAD"], OPC["STORE"])
            and linked.scalar[s, p, 3] >= 0)
        for s in range(linked.II))
    assert static_peak >= 1
    mem = program.random_inputs(np.random.default_rng(0))
    flat = program.flatten(mem)

    unlimited = replace(linked, n_mem_ports=0)
    sim = BatchedSimulator(unlimited)
    _, stats = sim.run(flat[None, :].copy(), program.n_iters,
                       check_ports=True)    # limit 0 short-circuits: no raise
    assert stats.max_mem_ports_used == static_peak
    assert not stats.oversubscribed
    rep = verify(linked=unlimited, program=program)
    assert "UAL011" in rep.codes() and rep.ok     # info-severity only

    strangled = replace(linked, n_mem_ports=1)
    if static_peak > 1:
        with pytest.raises(RuntimeError, match="port"):
            BatchedSimulator(strangled).run(flat[None, :].copy(),
                                            program.n_iters,
                                            check_ports=True)
        assert "UAL001" in verify(linked=strangled,
                                  program=program).codes()
