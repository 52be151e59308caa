"""System-level test: the UAL end-to-end driver path."""
import numpy as np


def test_ual_system_flow_shares_session_cache(ual_cache):
    """The UAL end-to-end driver path: Program -> Target -> compile ->
    run/validate, with the compile memoized in the session cache (same
    cache every other test file uses, so the kernel maps at most once
    per test session)."""
    from repro import ual
    program = ual.Program.from_kernel("nw")
    target = ual.Target.from_name("hycube", rows=4, cols=4)
    misses0 = ual_cache.stats.misses
    exe = ual.compile(program, target)
    assert exe.success
    rep = exe.validate(seed=1, backends=("sim", "pallas"))
    assert rep.passed and rep.backend_results == {"sim": True, "pallas": True}
    # an identical recompile must be a pure cache hit
    hits0 = ual_cache.stats.hits
    exe2 = ual.compile(program, target)
    assert exe2.compile_info.cache_hit
    assert exe2.compile_info.mapper_restarts == 0
    assert ual_cache.stats.hits == hits0 + 1
    assert ual_cache.stats.misses <= misses0 + 1
    # dict-in/dict-out execution round-trips the named I/O spec
    out = exe2.run(**program.random_inputs(np.random.default_rng(0)))
    assert set(out) == set(program.arrays)
