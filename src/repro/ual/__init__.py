"""Unified abstraction layer (UAL): the repo's stable public API.

The paper closes with a call for "a unified abstraction layer for CGRAs
and spatial accelerators, one that decouples hardware specialization from
software development".  This package is that layer::

    from repro import ual

    program = ual.Program.from_builder(b, n_iters=16)   # what to run
    target = ual.Target.from_name("hycube", rows=4, cols=4)  # where
    exe = ual.compile(program, target)                  # cached pipeline
    out = exe.run(a=a, b=b)                             # dict in/out
    report = exe.validate(backends=("sim", "pallas"))   # vs the oracle

    sweep = ual.explore(program, {                      # parallel DSE
        "fabric": ["pace", ("hycube", dict(rows=4, cols=4))],
        "strategy": ["adaptive", "sa"],
    }, workers=4)
    print(sweep.render())                               # Pareto report

Vocabulary:

  * ``Program``  — DFG + scratchpad layout + named I/O spec, content-hashed,
  * ``Target``   — fabric + mapper strategy + backend name,
  * ``compile``  — the staged pass pipeline (layout -> MII bounds ->
    mapping strategy -> lowering -> verify -> validation binding;
    per-pass timings in ``CompileInfo.passes``), memoized across
    processes by ``(program.digest, target.digest)`` — both the mapping
    and the lowered dense tables (``LinkedConfig``), so warm compiles
    neither re-map nor re-lower,
  * ``verify``/``CheckReport`` — the compile-time config verifier
    (``repro.analysis.verifier``): static diagnostics (``UAL001``...)
    over the lowered tables; error findings abort ``compile()`` with a
    rendered ``VerifyError``, warnings ride on
    ``Executable.check_report``; ``python -m repro.ual.check`` is the
    CLI (code reference: ``docs/diagnostics.md``),
  * ``Executable`` — ``run``/``run_batch``/``validate`` on any backend;
    ``run_batch`` is natively batched on ``sim`` and ``pallas`` and
    reports throughput (``last_info["throughput_sps"]``),
  * ``compile_many``/``explore`` — grid compilation over a process pool
    with cache-aware dedup, and the Pareto DSE front-end on top of it,
  * ``CompiledKernelCache``/``default_engine`` — the persistent JIT
    execution engine behind the ``pallas`` backend
    (``repro.ual.engine``): linked tables device-resident per engine,
    ``n_iters`` traced, batch sizes padded up a bucket ladder — trace
    once, run many (``Executable.warmup(buckets=...)`` pre-traces the
    ladder),
  * ``Service``  — the dynamic-batching execution service
    (``repro.ual.service``): single-sample requests are queued, coalesced
    into micro-batches per ``(program.digest, target.digest)`` class and
    executed as one ``run_batch`` sweep on shared warm Executables;
    ``submit`` returns a ``Response`` future, overload and expired
    deadlines come back as ``ServiceRejected`` verdicts, and
    ``Service.stats()`` reports p50/p99 latency, achieved batch size,
    samples/s, queue depth and rejects; ``submit_stream`` is the bulk
    path — one chunked request pipelined through a warm trace
    (``StreamResponse``), stream stats under ``stats()["stream"]``.

Extension points, all the same shape (named registry, duplicate names
raise without ``overwrite=True``): ``register_backend``
(interp/sim/pallas built-in), ``register_fabric``
(hycube/n2n/pace/spatial built-in) and ``register_strategy``
(adaptive/sa built-in); enumerate with ``list_backends()`` /
``list_fabrics()`` / ``list_strategies()``.
"""
from repro.analysis.verifier import (CheckReport, Diagnostic, VerifyError,
                                     verify)
from repro.core.lowering import LinkedConfig, link_config
from repro.core.mapper import (MapperStrategy, list_strategies,
                               register_strategy)
from repro.ual.backends import (Backend, get_backend, list_backends,
                                register_backend)
from repro.ual.cache import (CACHE_VERSION, CacheStats, MappingCache,
                             default_cache, default_cache_dir,
                             set_default_cache)
from repro.ual.cluster import ClusterService, RestartPolicy, Router
from repro.ual.compiler import compile
from repro.ual.faults import FaultPlan, FaultSpec, InjectedFault
from repro.ual.engine import (CompiledKernelCache, KernelEngine,
                              ShardedKernelEngine, bucket_ladder,
                              default_engine, set_default_engine)
from repro.ual.executable import CompileInfo, Executable, PassRecord
from repro.ual.explore import (DesignPoint, ExploreReport, compile_many,
                               explore)
from repro.ual.pipeline import (CompileContext, CompilePass, Pipeline,
                                VerifyPass, default_pipeline)
from repro.ual.program import Program
from repro.ual.service import (Response, Service, ServiceRejected,
                               StreamResponse)
from repro.ual.service.breaker import CircuitBreaker
from repro.ual.target import (FABRICS, Target, list_fabrics, register_fabric)

__all__ = [
    "Backend", "CACHE_VERSION", "CacheStats", "CheckReport",
    "CircuitBreaker", "ClusterService", "CompileContext", "CompileInfo",
    "CompiledKernelCache", "CompilePass", "DesignPoint", "Diagnostic",
    "Executable", "ExploreReport", "FABRICS", "FaultPlan", "FaultSpec",
    "InjectedFault", "KernelEngine", "LinkedConfig", "MapperStrategy",
    "MappingCache", "PassRecord", "Pipeline", "Program", "Response",
    "RestartPolicy", "Router", "Service", "ServiceRejected",
    "ShardedKernelEngine", "StreamResponse", "Target",
    "VerifyError", "VerifyPass",
    "bucket_ladder", "compile", "compile_many", "default_cache",
    "default_cache_dir", "default_engine", "default_pipeline", "explore",
    "get_backend", "link_config", "list_backends", "list_fabrics",
    "list_strategies", "register_backend", "register_fabric",
    "register_strategy", "set_default_cache", "set_default_engine",
    "verify",
]
