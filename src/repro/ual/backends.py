"""Pluggable execution backends for the unified abstraction layer.

A backend turns ``(Program, MapResult, named arrays)`` into named output
arrays.  Three ship with the repo:

  * ``interp``  — the DFG interpreter oracle (no mapping required; the
    reference semantics every other backend must match bit-exactly),
  * ``sim``     — the vectorized, natively-batched simulator executing
    the lowered configuration tables (``core.simulator.simulate_batch``),
  * ``pallas``  — the Pallas ``cgra_exec`` TPU kernel executing the same
    tables (batched; compiled on a TPU, interpreted on any other
    platform) through the persistent JIT
    engine (``repro.ual.engine``): trace-once/run-many with batch-bucket
    padding, tables device-resident per engine, ``n_iters`` traced, and
    only the program's declared arrays moved between host and device.

``sim`` and ``pallas`` both consume the shared **lowered artifact**
(``core.lowering.LinkedConfig``) produced once by the compile pipeline's
lowering pass: backends that set ``consumes_lowered = True`` receive it
via the ``lowered`` keyword — the tables are a pure function of the
machine configuration and the program's layout (each memory slot's row
range, ``mem_rows``), so custom device backends can execute them
directly instead of re-deriving routing from the raw config.

Third parties extend the layer with ``register_backend("mine", MyBackend())``
— see ROADMAP.md for a worked example.  Backends are resolved by name at
``compile()`` time; unknown names raise with the list of registered ones.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.dfg import interpret
from repro.core.mapper import MapResult
from repro.ual.program import Program

Mem = Dict[str, np.ndarray]
Info = Dict[str, object]


class Backend:
    """Base class: subclass and override ``execute`` (and optionally
    ``execute_batch`` when the device can batch natively)."""

    #: whether ``compile()`` must produce a machine configuration first
    requires_config: bool = True
    #: backends that execute the lowered dense tables set this to True and
    #: accept a ``lowered=`` keyword (a ``core.lowering.LinkedConfig``) in
    #: ``execute``/``execute_batch``; backends that interpret the raw
    #: config (or need no config at all) leave it False and keep the plain
    #: four-argument signature
    consumes_lowered: bool = False
    #: backends that can pin one call to one jax device accept a
    #: ``device=`` keyword in ``execute``/``execute_batch`` — the
    #: serving cluster's replica router uses this to run per-device
    #: replicas; leave False to never receive the keyword
    supports_device: bool = False
    #: natively-batched backends that can skip re-flattening when the
    #: caller already holds the (B, total_words) image accept a
    #: ``flats=`` keyword in ``execute_batch`` — ``Executable.validate``
    #: uses this to flatten its test vectors ONCE per multi-backend sweep
    accepts_flats: bool = False

    def execute(self, program: Program, result: Optional[MapResult],
                mem: Mem, n_iters: int, **kw) -> Tuple[Mem, Info]:
        raise NotImplementedError

    def execute_batch(self, program: Program, result: Optional[MapResult],
                      mems: List[Mem], n_iters: int, **kw
                      ) -> Tuple[List[Mem], Info]:
        outs = []
        info: Info = {}
        for m in mems:
            out, info = self.execute(program, result, m, n_iters, **kw)
            outs.append(out)
        return outs, info

    def execute_stream(self, program: Program, result: Optional[MapResult],
                       mems: Iterable[Mem], n_iters: int, *,
                       chunk: Optional[int] = None, **kw
                       ) -> Iterator[Tuple[List[Mem], Info]]:
        """Streaming execution: yield ``(out_dicts, chunk_info)`` per
        chunk of ``chunk`` samples as results drain; the generator's
        return value is the stream summary (must carry ``overlap_frac``
        and ``stream_chunks``).

        This default chunks the input through ``execute_batch`` — chunked
        delivery, but NO transfer/compute overlap (``overlap_frac`` 0.0).
        Backends with an asynchronous device path (pallas) override it
        with a genuinely pipelined implementation.
        """
        step = max(1, int(chunk) if chunk else 32)
        n_chunks = 0
        n_samples = 0
        group: List[Mem] = []
        for m in mems:
            group.append(m)
            if len(group) >= step:
                outs, info = self.execute_batch(program, result, group,
                                                n_iters, **kw)
                yield outs, {"chunk": n_chunks, "samples": len(outs),
                             **info}
                n_chunks += 1
                n_samples += len(outs)
                group = []
        if group:
            outs, info = self.execute_batch(program, result, group,
                                            n_iters, **kw)
            yield outs, {"chunk": n_chunks, "samples": len(outs), **info}
            n_chunks += 1
            n_samples += len(outs)
        return {"stream_chunks": n_chunks, "samples": n_samples,
                "overlap_frac": 0.0, "streamed": "chunked-sync"}


class InterpBackend(Backend):
    """DFG-interpreter oracle: executes the *pre-layout* DFG directly."""

    requires_config = False

    def execute(self, program, result, mem, n_iters):
        program.check_arrays(mem)
        return interpret(program.dfg, mem, n_iters), {}


def _ensure_lowered(result, lowered):
    """The shared artifact, or (for callers bypassing the pipeline) the
    per-process fingerprint memo — no path lowers one config twice."""
    if lowered is not None:
        return lowered
    from repro.kernels.cgra_exec.ops import _memoized_link
    return _memoized_link(result.config)


class SimBackend(Backend):
    """Vectorized, natively-batched simulation of the lowered tables.

    Consumes the shared lowered artifact; a single ``execute_batch`` call
    steps the whole batch through the fabric simultaneously (leading
    batch axis in the engine state).  The scalar reference engine remains
    available as ``core.simulator.simulate_reference``.
    """

    consumes_lowered = True
    accepts_flats = True

    def execute(self, program, result, mem, n_iters, lowered=None):
        from repro.core.simulator import simulate_batch
        flat = program.flatten(mem)
        out, stats = simulate_batch(_ensure_lowered(result, lowered),
                                    flat[None], n_iters)
        return program.unflatten(out[0]), {"sim_stats": stats,
                                           "engine": "vectorized"}

    def execute_batch(self, program, result, mems, n_iters, lowered=None,
                      flats=None):
        from repro.core.simulator import simulate_batch
        if flats is None:
            flats = program.flatten_batch(mems)
        outs, stats = simulate_batch(_ensure_lowered(result, lowered),
                                     flats, n_iters)
        return (program.unflatten_batch(outs),
                {"sim_stats": stats, "engine": "vectorized", "batched": True})


class PallasBackend(Backend):
    """Pallas ``cgra_exec`` TPU kernel (interpreted off-TPU), executed
    through the persistent JIT engine (``repro.ual.engine``): the linked
    tables live on device per engine, ``n_iters`` is traced, and batch
    sizes are padded up the bucket ladder so repeat traffic hits warm
    traces — trace once, run many.  Blocks carry only the words of the
    program's declared arrays (``Program.live``, packed by
    ``Program.pack_batch``): the engine scatters them into the zeroed
    (M, bucket) scratchpad on the device and gathers them back, so host
    and device exchange L words an image, not the scratchpad's M.

    Two multi-device modes (the serving cluster's substrates):

      * default (``sharded=False``): accepts a per-call ``device=``
        keyword (``supports_device``) routing the sweep through a
        device-pinned replica engine — N calls on N devices run truly
        concurrent replicas;
      * ``sharded=True`` (registered as ``"pallas_sharded"``): every
        sweep shard_maps the batch axis over ALL host devices through
        one ``ShardedKernelEngine`` — one trace, N devices, per-device
        bucket padding.
    """

    consumes_lowered = True
    accepts_flats = True

    def __init__(self, lanes: int = 128, engine=None,
                 sharded: bool = False):
        self.lanes = lanes
        self._engine = engine        # None -> the process-wide engine cache
        self.sharded = sharded
        # a sharded sweep spans every device; pinning it to one is a
        # contradiction, so the router never offers the keyword
        self.supports_device = not sharded

    @property
    def engine(self):
        if self._engine is not None:
            return self._engine
        from repro.ual.engine import default_engine
        return default_engine()

    def execute(self, program, result, mem, n_iters, lowered=None,
                device=None):
        outs, info = self.execute_batch(program, result, [mem], n_iters,
                                        lowered=lowered, device=device)
        return outs[0], info

    def _engine_for(self, linked, device=None):
        """The (cached) engine executing ``linked`` under this backend's
        opts — sharded or single-device, per the registration."""
        if self.sharded:
            return self.engine.sharded_engine_for(linked, lanes=self.lanes)
        return self.engine.engine_for(linked, lanes=self.lanes,
                                      device=device)

    def execute_batch(self, program, result, mems, n_iters, lowered=None,
                      device=None, flats=None):
        block = (program.pack_batch(mems) if flats is None
                 else program.pack_flats(flats))
        packing = dict(live=program.live, M=program.layout.total_words)
        linked = _ensure_lowered(result, lowered)
        if self.sharded:
            out, info = self.engine.sharded_run(linked, block, n_iters,
                                                lanes=self.lanes, **packing)
        else:
            out, info = self.engine.run(linked, block, n_iters,
                                        lanes=self.lanes, device=device,
                                        **packing)
        info["batched"] = True
        return program.unpack_batch(out), info

    def execute_stream(self, program, result, mems, n_iters, *,
                       chunk=None, lowered=None, device=None):
        """Genuinely pipelined streaming: chunks flow through the
        persistent engine's double-buffered ``run_stream`` as packed
        (b, L) blocks of the program's live words — while chunk *i*
        computes on device, the host packs/uploads chunk *i+1* and
        unpacks chunk *i-1*'s drained rows.  Same bucket-ladder traces
        as ``execute_batch``; the summary carries the engine's measured
        ``overlap_frac``.  With the tracer on, each block adds
        ``stream:flatten`` (the pack) and ``stream:unflatten`` (the
        unpack) spans to the engine's stream trace."""
        linked = _ensure_lowered(result, lowered)
        eng = self._engine_for(linked, device=device)
        step = (max(1, min(int(chunk), eng._capacity())) if chunk
                else eng._capacity())
        tr = obs.tracer()
        trace = tr.new_trace_id() if tr.enabled else None

        def flatten(group):
            with tr.span("stream:flatten", "engine", trace=trace):
                return program.pack_batch(group)

        def blocks():
            group = []
            for m in mems:
                group.append(m)
                if len(group) >= step:
                    yield flatten(group)
                    group = []
            if group:
                yield flatten(group)

        gen = eng.run_stream(blocks(), n_iters, chunk=step, trace=trace,
                             live=program.live,
                             M=program.layout.total_words)
        while True:
            try:
                out, cinfo = next(gen)
            except StopIteration as stop:
                summary = dict(stop.value or {})
                summary["batched"] = True
                return summary
            with tr.span("stream:unflatten", "engine", trace=trace):
                outs = program.unpack_batch(out)
            yield outs, cinfo

    def warmup(self, program, result, lowered=None, buckets=None,
               device=None):
        """Pre-trace the bucket ladder for this program's packed blocks
        (``n_iters`` is traced, so one trace per bucket covers every trip
        count).  Returns the engine's stats."""
        linked = _ensure_lowered(result, lowered)
        eng = self._engine_for(linked, device=device)
        return eng.warmup(program.layout.total_words, buckets,
                          live=program.live)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Backend] = {}


def register_backend(name: str, backend: Backend,
                     overwrite: bool = False) -> None:
    """Register an execution backend under ``name``.

    Registering an existing name raises unless ``overwrite=True`` — silent
    replacement is how two plugins stomp each other.
    """
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    if not isinstance(backend, Backend):
        raise TypeError(f"backend must be a ual.backends.Backend, "
                        f"got {type(backend).__name__}")
    _BACKENDS[name] = backend


def get_backend(name: str) -> Backend:
    if name not in _BACKENDS:
        raise KeyError(f"unknown backend {name!r}; "
                       f"registered: {sorted(_BACKENDS)}")
    return _BACKENDS[name]


def list_backends() -> List[str]:
    return sorted(_BACKENDS)


register_backend("interp", InterpBackend())
register_backend("sim", SimBackend())
register_backend("pallas", PallasBackend())
register_backend("pallas_sharded", PallasBackend(sharded=True))
