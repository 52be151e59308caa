"""Persistent JIT execution engine: trace-once / run-many for the pallas path.

The ``pallas`` backend used to pay full tracing + lowering cost on every
call — ``cgra_exec`` rebuilt its ``pallas_call`` per invocation with the
batch size and trip count baked in as Python constants, and re-uploaded
the linked tables each time.  The paper's abstraction-layer bet (and
HyCUBE's CM-resident-on-chip bet, Morpher's map-once/simulate-many split)
is the opposite: produce the compiled artifact ONCE, execute it many
times.  This module is that half of the story:

  * ``CompiledKernelCache`` — the engine registry, keyed on
    ``(lowered fingerprint, lanes, placement)`` with per-``(M, bucket)``
    trace entries below that: the full key of one compiled trace is
    ``(lowered fingerprint, lanes, placement, batch bucket)``.  The kernel
    runs compiled on a TPU and interpreted elsewhere — the platform
    decides (``kernels.cgra_exec.kernel.interpret_mode``), no caller does,
  * each ``KernelEngine`` wraps the shared ``cgra_exec`` kernel body in
    ONE ``jax.jit`` with the linked tables uploaded to device once and
    closed over as constants (the CM-in-VMEM analogue at the host level),
  * ``n_iters`` is a *traced* scalar operand (dynamic ``fori_loop`` bound
    + fired-masking inside the kernel), so one trace serves every
    iteration count,
  * batch sizes are padded up a small **bucket ladder** (default
    ``1, 8, 32, lanes``): the execution service's variable-sized
    micro-batches hit warm traces instead of retracing per shape, and
    batches beyond the largest bucket run as warm largest-bucket chunks —
    the trace count stays O(#buckets) no matter how traffic is shaped.

Packed blocks: a program's declared arrays cover a small part of its
scratchpad (129 of 8,192 words for gemm), so the host moves only those
words.  ``run``/``run_stream`` take ``live=`` (``Program.live``: the
``(base, length)`` runs of the arrays' words) and ``M=``: each image is
then a row of L = sum of the run lengths words.  Inside the one jit the
block is scattered into a zeroed (M, bucket) scratchpad — what the
kernel works on, unchanged — and the same rows are gathered back out
after it, so upload and copy-back carry L words an image, not M.
Without ``live`` a block is a (bucket, M) row of whole images, for
direct callers that hold them.

Streaming (the STRELA mode — data flows through a resident config):
``run`` is upload -> sweep -> download in strict sequence, so on large
batches the host<->device transfer time is dead time.  ``run_stream``
instead pipelines warm-bucket chunks with **double buffering**: jax
dispatch is asynchronous, so while chunk *i* computes on device the
host pads/uploads chunk *i+1* and converts chunk *i-1*'s drained
results — the same bucket-ladder traces (zero new traces), with the
transfer work overlapped against compute.  Chunks are yielded as they
drain; the generator's return value reports ``overlap_frac`` (fraction
of wall time the host spent working instead of blocked on the device),
``stream_chunks`` and throughput.

Observability: every engine counts traces, calls, per-bucket hits,
padding waste, streaming activity (``streams``/``stream_chunks``), the
words its blocks move (``transfer_words``) and the kernel's work
(``fabric_cycles``/``mem_passes``/``mem_chunks``, host arithmetic);
``CompiledKernelCache.stats()`` aggregates them (the execution service
surfaces this in ``Service.stats()["engine"]``, and
``Executable.warmup()`` reports it in ``last_info``).

Multi-device (the serving-cluster substrate, ``repro.ual.cluster``):

  * ``KernelEngine(device=...)`` pins one engine to one device — tables
    and inputs are committed there, so N engines on N devices execute
    truly independent replicas (the Router's ReplicaPool path),
  * ``ShardedKernelEngine`` ``shard_map``s the *batch axis* of the same
    kernel over the host's 1-D ``data`` mesh
    (``launch.mesh.make_host_mesh``): tables are replicated once, each
    device runs one per-device bucket block, and ONE trace drives all
    local devices.  Padding is per-device — a global block is
    ``n_devices x bucket_for(ceil(chunk / n_devices))`` rows — so the
    bucket-ladder trace economy survives sharding unchanged.  Engines
    are cached per ``(fingerprint, lanes, placement)`` via
    ``engine_for(device=...)`` / ``sharded_engine_for``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import (Dict, Iterable, Iterator, List, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from repro import obs
from repro.core.dfg import Runs
from repro.core.lowering import (LinkedConfig, kernel_rounds, kernel_tables,
                                 lowered_fingerprint, mem_chunk_counts,
                                 state_copy_counts)


def make_cgra_call(*args, **kwargs):
    """Lazy indirection to the shared ``pallas_call`` constructor: keeps
    ``import repro.ual`` free of the jax import (fork-based tooling like
    ``compile_many`` must be able to spawn workers before jax starts its
    threads), while tests can still monkeypatch-count traces here."""
    from repro.kernels.cgra_exec.kernel import make_cgra_call as real
    return real(*args, **kwargs)


def _scatter(block, live: Runs, M: int):
    """A packed (rows, L) ``block`` -> (rows, M) whole images (traced):
    its columns, run after run, land on the words ``live`` names, and
    every other word is zero."""
    import jax.numpy as jnp
    mem = jnp.zeros((block.shape[0], M), jnp.int32)
    col = 0
    for lo, n in live:
        # each run zero-padded to the whole image and summed: one fused
        # pass on the TPU, where a concatenate of several runs lowers to
        # in-place updates of a buffer from an ``AllocateBuffer`` custom
        # call, a second custom call beside the kernel's in the program
        mem = mem + jnp.pad(block[:, col:col + n], ((0, 0), (lo, M - lo - n)))
        col += n
    return mem


def _gather(mem, live: Runs):
    """(rows, M) whole images -> the (rows, L) block of their ``live``
    words (traced)."""
    import jax.numpy as jnp
    return jnp.concatenate([mem[:, lo:lo + n] for lo, n in live]
                           + [mem[:, :0]], axis=1)


def _packing(width: int, live: Optional[Sequence[Tuple[int, int]]],
             M: Optional[int]) -> Tuple[Runs, int]:
    """``(live, M)`` for blocks ``width`` words an image wide: without
    ``live`` a block is whole images (its own scratchpad); with it the
    runs must be ascending, disjoint, inside ``[0, M)`` and ``width``
    words in all."""
    if live is None:
        if M not in (None, width):
            raise ValueError(f"a full-width block is {width} words an "
                             f"image, not M={M}")
        return ((0, width),), width
    live = tuple((int(lo), int(n)) for lo, n in live)
    end = 0
    for lo, n in live:
        if lo < end or n < 1:
            raise ValueError(f"live runs {live} are not ascending and "
                             f"disjoint")
        end = lo + n
    if M is None or end > M or sum(n for _, n in live) != width:
        raise ValueError(f"live runs {live} do not fit a {width}-word "
                         f"block of an M={M} scratchpad")
    return live, int(M)


def bucket_ladder(lanes: int = 128,
                  buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """The batch-size ladder: ascending, deduplicated, capped at ``lanes``
    (one VPU tile — bigger batches run as warm largest-bucket chunks)."""
    if buckets is None:
        buckets = (1, 8, 32, lanes)
    ladder = sorted({int(b) for b in buckets if 1 <= int(b) <= lanes})
    if not ladder:
        raise ValueError(f"bucket ladder {buckets!r} has no entry in "
                         f"[1, lanes={lanes}]")
    return tuple(ladder)


class KernelEngine:
    """One persistent engine: a lowered artifact + backend opts.

    Owns the device-resident tables (uploaded once, closed over as jit
    constants) and the single jitted entry point; ``jax.jit`` specializes
    it per ``(M, bucket, live)`` (the live runs and M are static), and
    the ladder keeps that set small.

    ``device=`` pins the engine (tables AND per-call operands) to one
    device — the replica path: N pinned engines on N host devices
    execute concurrently with zero shared state.
    """

    ENGINE_NAME = "pallas-jit"
    #: kernel calls one block runs (one per device of the sharded engine)
    n_devices = 1

    def _info_extra(self) -> Dict[str, object]:
        """Engine-flavor extras merged into per-call info and stats."""
        return {}

    def __init__(self, linked: LinkedConfig, *, lanes: int = 128,
                 buckets: Optional[Sequence[int]] = None,
                 device=None) -> None:
        import jax
        import jax.numpy as jnp

        from repro.kernels.cgra_exec.kernel import interpret_mode

        self.linked = linked
        self.lanes = lanes
        #: what actually runs: the Pallas interpreter, or the
        #: Mosaic-compiled kernel on a TPU
        self.mode = "interp" if interpret_mode() else "tpu"
        self.device = device          # None -> jax default placement
        self.buckets = bucket_ladder(lanes, buckets)
        self.fingerprint = lowered_fingerprint(linked)
        self._jax = jax
        self._jnp = jnp
        # upload the CM image once per engine; every trace closes over
        # these device arrays as constants — never re-fed per call
        self._tables = self._put_tables(linked)
        # counters: traces bumps at TRACE time (a Python side effect of
        # the traced function), so it counts actual retraces, not calls.
        # Two locks: _trace_lock serializes cold traces (held for seconds),
        # _stats_lock guards the counters and the warm-shape set (held for
        # nanoseconds) so concurrent Service workers never lose an update
        # and stats() never iterates a mutating set
        self.traces = 0
        self.calls = 0
        self.samples = 0
        self.padded_samples = 0
        self.streams = 0             # run_stream invocations completed
        self.stream_chunks = 0       # chunks drained across all streams
        # int32 words blocks moved host->device plus device->host,
        # padding rows included
        self.transfer_words = 0
        # the kernel's work, counted on the host per dispatched block
        # (``_count_work``): fabric cycles of the real images, scratchpad
        # passes and the chunk steps those passes run
        self.fabric_cycles = 0
        self.mem_passes = 0
        self.mem_chunks = 0
        self._chunks_per_iter: Dict[int, int] = {}    # by M
        self.bucket_calls: Dict[int, int] = {}
        self._warm: set = set()        # (M, bucket, live) already traced
        self._trace_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._fn = jax.jit(self._packed, static_argnames=("live", "M"))

    # -- placement (overridden by the sharded engine) -------------------------
    def _put_tables(self, linked: LinkedConfig) -> tuple:
        """Upload the kernel's CM image to this engine's placement."""
        jax, jnp = self._jax, self._jnp
        return tuple(
            jax.device_put(jnp.asarray(t, jnp.int32), self.device)
            for t in kernel_tables(linked))

    def _put_operand(self, arr):
        """One per-call operand (niter / mem block) onto the placement.
        Committed explicitly when the engine is device-pinned, so jit
        runs on THAT device instead of moving everything to the default."""
        if self.device is None:
            return self._jnp.asarray(arr)
        return self._jax.device_put(self._jnp.asarray(arr), self.device)

    # -- the traced functions -------------------------------------------------
    def _packed(self, niter, block, live: Runs, M: int):
        """The jitted entry: ``block`` is one padded (bucket, L) block of
        the ``live`` words of M-word images.  It becomes zero-filled whole
        images on the device, runs through ``_traced`` and comes back as
        the same words; retraced per ``(M, bucket, live)``."""
        return _gather(self._traced(niter, _scatter(block, live, M)), live)

    def _traced(self, niter, mem):
        """``mem`` is one padded (bucket, M) block of whole images: the
        kernel runs on its transpose, the (M, bucket) scratchpad."""
        self.traces += 1
        bucket, M = mem.shape
        call = make_cgra_call(self.linked, M=M, bB=bucket, n_tiles=1)
        return call(niter, *self._tables, mem.T).T

    # -- execution ------------------------------------------------------------
    def _count_work(self, n_iters: int, images: int, blocks: int,
                    M: int) -> None:
        """Add the work of ``blocks`` (·, ``M``) blocks carrying ``images``
        real images for ``n_iters`` to the counters (under the stats
        lock): every image runs the kernel's rounds of II cycles, and
        every kernel call one pass per fired LOAD/STORE slot, over the
        chunks of its row range (``mem_chunk_counts``)."""
        L = self.linked
        calls = n_iters * blocks * self.n_devices
        self.fabric_cycles += (kernel_rounds(n_iters, L.II, L.t0_max)
                               * L.II * images)
        self.mem_passes += L.mem_slots * calls
        if M not in self._chunks_per_iter:
            self._chunks_per_iter[M] = mem_chunk_counts(L, M)[0]
        self.mem_chunks += self._chunks_per_iter[M] * calls

    def bucket_for(self, b: int) -> int:
        """Smallest ladder bucket >= b (callers chunk at the largest)."""
        for bk in self.buckets:
            if bk >= b:
                return bk
        return self.buckets[-1]

    # -- the block plan (overridden by the sharded engine) --------------------
    def _capacity(self) -> int:
        """Rows one block can carry; ``run`` chunks bigger batches."""
        return self.buckets[-1]

    def _block_rows(self, chunk: int) -> int:
        """Padded row count the block for ``chunk`` samples executes at
        (``chunk <= _capacity()``).  The sharded engine pads per device:
        ``n_devices * bucket_for(ceil(chunk / n_devices))``."""
        return self.bucket_for(chunk)

    def _dispatch_block(self, block: np.ndarray, niter, live: Runs, M: int
                        ) -> Tuple[object, bool]:
        """Asynchronously dispatch one padded block; returns the device
        future WITHOUT materializing it, and whether THIS call found the
        shape untraced (info attribution stays per-call under
        concurrency).  Warm shapes return immediately (jax async
        dispatch); cold ``(M, bucket, live)`` shapes trace synchronously
        under the trace lock, so concurrent workers pay exactly one trace
        per bucket — a cold trace takes seconds and must not sit in the
        pipeline as if it were a 1 ms hop."""
        key = (M, block.shape[0], live)
        with self._stats_lock:
            warm = key in self._warm
        if warm:
            return self._fn(niter, self._put_operand(block), live=live,
                            M=M), False
        with self._trace_lock:
            fut = self._fn(niter, self._put_operand(block), live=live, M=M)
            fut.block_until_ready()
            with self._stats_lock:
                self._warm.add(key)
        return fut, True

    def run(self, flats: np.ndarray, n_iters: int, *,
            live: Optional[Sequence[Tuple[int, int]]] = None,
            M: Optional[int] = None
            ) -> Tuple[np.ndarray, Dict[str, object]]:
        """Execute a batch for ``n_iters``: (B, M) whole scratchpad
        images, or with ``live`` and ``M`` a (B, L) packed block of the
        ``live`` words (``Program.pack_batch``).

        Pads each chunk up the bucket ladder (B > largest bucket runs as
        warm largest-bucket chunks) and slices the padding back off;
        returns ``(out, per-call info)``, ``out`` shaped like ``flats``.
        """
        jnp = self._jnp
        flats = np.ascontiguousarray(flats, np.int32)
        B, width = flats.shape
        live, M = _packing(width, live, M)
        niter = self._put_operand(
            jnp.asarray(n_iters, jnp.int32).reshape(1, 1))
        used: List[int] = []
        cold_blocks = 0
        top = self._capacity()
        if B <= top and self._block_rows(B) == B:
            # pad-free fast path: the batch IS a bucket — no padding
            # rows to append, no staging buffer to copy through
            fut, was_cold = self._dispatch_block(flats, niter, live, M)
            out = np.asarray(fut)
            cold_blocks = int(was_cold)
            used.append(B)
        else:
            out = np.empty((B, width), np.int32)
            i = 0
            while i < B:
                chunk = min(B - i, top)
                rows = self._block_rows(chunk)
                block = flats[i:i + chunk]
                if rows != chunk:
                    block = np.concatenate(
                        [block, np.zeros((rows - chunk, width), np.int32)])
                fut, was_cold = self._dispatch_block(block, niter, live, M)
                out[i:i + chunk] = np.asarray(fut)[:chunk]
                cold_blocks += was_cold
                used.append(rows)
                i += chunk
        with self._stats_lock:
            for rows in used:
                self.bucket_calls[rows] = \
                    self.bucket_calls.get(rows, 0) + 1
            self.padded_samples += sum(used) - B
            self.calls += 1
            self.samples += B
            self.transfer_words += 2 * width * sum(used)
            self._count_work(n_iters, B, len(used), M)
            traces_total = self.traces
        info = {
            "engine": self.ENGINE_NAME,
            "buckets": used,
            "padded": sum(used) - B,
            "traced": cold_blocks,
            "traces_total": traces_total,
            **self._info_extra(),
        }
        return out, info

    # -- streaming ------------------------------------------------------------
    def run_stream(self, source: Union[np.ndarray, Iterable[np.ndarray]],
                   n_iters: int, *, chunk: Optional[int] = None,
                   depth: int = 2, trace: Optional[str] = None,
                   live: Optional[Sequence[Tuple[int, int]]] = None,
                   M: Optional[int] = None
                   ) -> Iterator[Tuple[np.ndarray, Dict[str, object]]]:
        """Streaming execution: pipeline warm-bucket chunks with double
        buffering, yielding ``(out_chunk, chunk_info)`` as each chunk
        drains, ``out_chunk`` (b, W) like its input rows.

        ``source`` is a (B, W) batch or an iterable of (b, W) row blocks
        (blocks larger than ``chunk`` are re-chunked): whole (W = M)
        scratchpad images, or with ``live`` and ``M`` packed blocks of
        the ``live`` words (W = L, as ``run``).  While chunk *i*
        computes on device, the host pads/uploads chunk *i+1* and
        converts chunk *i-1*'s results — jax async dispatch keeps up to
        ``depth`` chunks in flight, so host<->device transfer work
        overlaps compute instead of serializing with it (``run``'s
        upload -> sweep -> download).  Chunks ride the same bucket-ladder
        traces as ``run``: a warmed engine streams with ZERO new traces.

        The generator's return value (``StopIteration.value``) is the
        stream summary: ``stream_chunks``, ``samples``, ``wall_s``,
        ``throughput_sps``, ``wait_s`` (host time blocked on the device)
        and ``overlap_frac`` = 1 - wait/wall — the fraction of the wall
        the host spent preparing/draining other chunks while the device
        worked.  A fully serialized pipeline (or an empty stream)
        reports 0.0.

        With the tracer on, each block records ``stream:upload`` (pad +
        dispatch), ``stream:wait`` (blocked on the device: the intervals
        ``wait_s`` sums) and ``stream:download`` (the copy back), all
        under one trace id per stream: ``trace``, or a new one.
        """
        jnp = self._jnp
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        top = self._capacity()
        step = top if chunk is None else max(1, min(int(chunk), top))
        niter = self._put_operand(
            jnp.asarray(n_iters, jnp.int32).reshape(1, 1))

        def blocks() -> Iterator[np.ndarray]:
            blks = [source] if isinstance(source, np.ndarray) else source
            for blk in blks:
                blk = np.ascontiguousarray(blk, np.int32)
                for i in range(0, len(blk), step):
                    yield blk[i:i + step]

        t_start = time.perf_counter()
        wait_s = 0.0
        used: List[int] = []
        cold_blocks = 0
        n_samples = 0
        n_chunks = 0
        n_dispatched = 0
        M_run = 0                  # the blocks' scratchpad rows
        words = 0                  # int32 words moved, both ways
        tr = obs.tracer()
        tron = tr.enabled
        # one trace groups every chunk span of this stream in the export
        if tron and trace is None:
            trace = tr.new_trace_id()
        inflight: deque = deque()  # (future, b, rows, was_cold)

        def drain() -> Tuple[np.ndarray, Dict[str, object]]:
            nonlocal wait_s, cold_blocks, n_samples, n_chunks
            fut, b, rows, was_cold = inflight.popleft()
            with tr.span("stream:wait", "engine", trace=trace):
                t0 = time.perf_counter()
                fut.block_until_ready()
                wait_s += time.perf_counter() - t0
            with tr.span("stream:download", "engine", trace=trace):
                out = np.asarray(fut)[:b]
            cold_blocks += was_cold
            used.append(rows)
            n_samples += b
            n_chunks += 1
            return out, {"chunk": n_chunks - 1, "bucket": rows,
                         "samples": b, "traced": int(was_cold)}

        for blk in blocks():
            b, width = blk.shape
            lv, M_run = _packing(width, live, M)
            with tr.span("stream:upload", "engine", trace=trace) as sp:
                rows = self._block_rows(b)
                if rows != b:
                    blk = np.concatenate(
                        [blk, np.zeros((rows - b, width), np.int32)])
                fut, was_cold = self._dispatch_block(blk, niter, lv, M_run)
                if tron:
                    sp.set(chunk=n_dispatched, bucket=rows, samples=b,
                           traced=int(was_cold))
            inflight.append((fut, b, rows, was_cold))
            words += 2 * width * rows
            n_dispatched += 1
            while len(inflight) > depth:
                yield drain()
        while inflight:
            yield drain()

        wall = time.perf_counter() - t_start
        with self._stats_lock:
            for rows in used:
                self.bucket_calls[rows] = self.bucket_calls.get(rows, 0) + 1
            self.padded_samples += sum(used) - n_samples
            self.calls += 1
            self.samples += n_samples
            self.transfer_words += words
            self._count_work(n_iters, n_samples, len(used), M_run)
            self.streams += 1
            self.stream_chunks += n_chunks
            traces_total = self.traces
        return {
            "engine": self.ENGINE_NAME,
            "stream_chunks": n_chunks,
            "samples": n_samples,
            "buckets": used,
            "padded": sum(used) - n_samples,
            "traced": cold_blocks,
            "traces_total": traces_total,
            "wall_s": wall,
            "wait_s": wait_s,
            "overlap_frac": (round(max(0.0, 1.0 - wait_s / wall), 4)
                             if wall > 0 and n_chunks else 0.0),
            "throughput_sps": n_samples / wall if wall > 0 else 0.0,
            **self._info_extra(),
        }

    def warmup(self, M: int,
               buckets: Optional[Sequence[int]] = None, *,
               live: Optional[Sequence[Tuple[int, int]]] = None
               ) -> Dict[str, object]:
        """Pre-trace the ladder (or a subset) for scratchpad width ``M``
        — whole images, or packed blocks of the ``live`` words — with a
        zero batch: ``n_iters`` is traced, so one warm trace per bucket
        covers every trip count.  Requested sizes off the engine's ladder
        snap UP to the bucket that will actually execute them
        (``bucket_for``), so re-warming is always a no-op.  Returns this
        engine's stats."""
        width = M if live is None else sum(int(n) for _, n in live)
        live, M = _packing(width, live, M)
        want = sorted({self._block_rows(min(b, self._capacity())) for b in
                       bucket_ladder(self.lanes, buckets or self.buckets)})
        for rows in want:
            with self._stats_lock:
                warm = (M, rows, live) in self._warm
            if not warm:
                self.run(np.zeros((rows, width), np.int32), 1, live=live,
                         M=M)
        return self.stats()

    # -- observability --------------------------------------------------------
    def stats(self) -> Dict[str, object]:
        with self._stats_lock:
            traces = self.traces
            bucket_calls = dict(sorted(self.bucket_calls.items()))
            snap = {
                "calls": self.calls,
                "samples": self.samples,
                "padded_samples": self.padded_samples,
                "streams": self.streams,
                "stream_chunks": self.stream_chunks,
                "transfer_words": self.transfer_words,
                "fabric_cycles": self.fabric_cycles,
                "mem_passes": self.mem_passes,
                "mem_chunks": self.mem_chunks,
                "warm_shapes": sorted(self._warm),
            }
        calls = sum(bucket_calls.values())
        hits = max(0, calls - traces)
        copied, dense = state_copy_counts(self.linked)
        return {
            "traces": traces,
            "bucket_calls": bucket_calls,
            "hit_ratio": round(hits / calls, 4) if calls else None,
            "buckets": self.buckets,
            "mode": self.mode,
            # where the tables (and so every sweep) live
            "platform": ",".join(sorted(
                {d.platform for d in self._tables[0].devices()})),
            # PE-state rows the kernel copies per round of the II slots,
            # against the rows a dense one-hot scan would step through
            "state_rows_copied_per_round": copied,
            "state_rows_dense_per_round": dense,
            **snap,
            **self._info_extra(),
        }


class ShardedKernelEngine(KernelEngine):
    """The multi-device engine: one trace drives all local devices.

    ``shard_map``s the batch axis of the persistent kernel over a 1-D
    ``data`` mesh (default: ``launch.mesh.make_host_mesh()`` — every
    device on the host).  The linked tables are uploaded once with a
    *replicated* sharding; each device executes one per-device bucket
    block of the batch, so a global block is
    ``n_devices x bucket_for(ceil(chunk / n_devices))`` rows and the
    bucket-ladder trace economy is unchanged — the warm-shape set and
    trace count stay O(#buckets) while throughput scales with the mesh.

    The replicated tables enter the ``jax.shard_map`` as operands with
    ``P()`` specs (closing over arrays placed on an Explicit mesh is not
    implemented), and ``check_vma=False``: pallas_call has no
    varying-manual-axes rule, and the body touches only per-device data.

    Parity contract: bit-exact with the single-device engine (and the
    interp oracle) for every batch size, including ragged final chunks —
    padding rows are zero blocks whose outputs are sliced off, exactly
    as in the single-device path.
    """

    ENGINE_NAME = "pallas-jit-sharded"

    def __init__(self, linked: LinkedConfig, *, lanes: int = 128,
                 buckets: Optional[Sequence[int]] = None,
                 mesh=None) -> None:
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        if mesh.devices.ndim != 1:
            raise ValueError(
                f"ShardedKernelEngine needs a 1-D mesh (the batch axis), "
                f"got shape {mesh.devices.shape}")
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_devices = int(mesh.devices.size)
        super().__init__(linked, lanes=lanes, buckets=buckets)

    def _info_extra(self) -> Dict[str, object]:
        return {"n_devices": self.n_devices}

    def _put_tables(self, linked: LinkedConfig) -> tuple:
        """The CM image once per device: replicated over the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec
        jax, jnp = self._jax, self._jnp
        rep = NamedSharding(self.mesh, PartitionSpec())
        return tuple(
            jax.device_put(jnp.asarray(t, jnp.int32), rep)
            for t in kernel_tables(linked))

    def _put_operand(self, arr):
        return self._jnp.asarray(arr)

    def _traced(self, niter, mem):
        """``mem`` is one (n_devices * bucket, M) global block of whole
        images (the packed entry around it is the single-device
        engine's, partitioned along the batch axis); each device's shard
        runs the same pallas_call at the per-device bucket shape — one
        trace, every device."""
        from jax.sharding import PartitionSpec as P
        jax = self._jax
        self.traces += 1
        rows, M = mem.shape
        bucket = rows // self.n_devices
        call = make_cgra_call(self.linked, M=M, bB=bucket, n_tiles=1)

        def shard_fn(niter, mem_shard, *tables):
            return call(niter, *tables, mem_shard.T).T

        tables = self._tables
        return jax.shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(P(), P(self.axis, None)) + (P(),) * len(tables),
            out_specs=P(self.axis, None),
            check_vma=False)(niter, mem, *tables)

    # -- the sharded block plan ----------------------------------------------
    def _capacity(self) -> int:
        return self.n_devices * self.buckets[-1]

    def _block_rows(self, chunk: int) -> int:
        per_device = -(-chunk // self.n_devices)      # ceil
        return self.n_devices * self.bucket_for(per_device)


class CompiledKernelCache:
    """The engine registry: one ``KernelEngine`` per
    ``(lowered fingerprint, lanes, placement)``, created on
    first use and kept for the life of the process — the
    trace-once/run-many cache the pallas backend, ``Executable.warmup``
    and the execution service share.  Placement distinguishes the default
    engine, device-pinned replica engines (``device=``) and the sharded
    multi-device engine (``sharded_engine_for``).
    """

    def __init__(self, buckets: Optional[Sequence[int]] = None) -> None:
        self.default_buckets = buckets
        self._engines: Dict[Tuple[str, int, Optional[str]],
                            KernelEngine] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _placement(device, mesh, sharded: bool) -> Optional[str]:
        if sharded:
            if mesh is None:
                return "sharded:host"
            return "sharded:" + ",".join(
                str(d.id) for d in mesh.devices.flat)
        return None if device is None else f"dev:{device.id}"

    def engine_for(self, linked: LinkedConfig, *, lanes: int = 128,
                   buckets: Optional[Sequence[int]] = None,
                   device=None) -> KernelEngine:
        key = (lowered_fingerprint(linked), lanes,
               self._placement(device, None, False))
        with self._lock:
            eng = self._engines.get(key)
            if eng is None:
                eng = KernelEngine(linked, lanes=lanes,
                                   buckets=buckets or self.default_buckets,
                                   device=device)
                self._engines[key] = eng
            return eng

    def sharded_engine_for(self, linked: LinkedConfig, *, lanes: int = 128,
                           buckets: Optional[Sequence[int]] = None,
                           mesh=None) -> ShardedKernelEngine:
        """The multi-device engine for ``linked`` (default mesh: every
        host device on a 1-D ``data`` axis), cached like ``engine_for``."""
        key = (lowered_fingerprint(linked), lanes,
               self._placement(None, mesh, True))
        with self._lock:
            eng = self._engines.get(key)
            if eng is None:
                eng = ShardedKernelEngine(
                    linked, lanes=lanes,
                    buckets=buckets or self.default_buckets, mesh=mesh)
                self._engines[key] = eng
            return eng

    def run(self, linked: LinkedConfig, flats: np.ndarray, n_iters: int, *,
            lanes: int = 128, device=None, live=None, M=None
            ) -> Tuple[np.ndarray, Dict[str, object]]:
        eng = self.engine_for(linked, lanes=lanes, device=device)
        return eng.run(flats, n_iters, live=live, M=M)

    def sharded_run(self, linked: LinkedConfig, flats: np.ndarray,
                    n_iters: int, *, lanes: int = 128, mesh=None,
                    live=None, M=None
                    ) -> Tuple[np.ndarray, Dict[str, object]]:
        eng = self.sharded_engine_for(linked, lanes=lanes, mesh=mesh)
        return eng.run(flats, n_iters, live=live, M=M)

    def run_stream(self, linked: LinkedConfig, source, n_iters: int, *,
                   chunk: Optional[int] = None, depth: int = 2,
                   lanes: int = 128, device=None, live=None, M=None
                   ) -> Iterator[Tuple[np.ndarray, Dict[str, object]]]:
        """Streaming execution through the cached engine for ``linked``
        (see ``KernelEngine.run_stream``); yields drained chunks, returns
        the stream summary via ``StopIteration.value``."""
        eng = self.engine_for(linked, lanes=lanes, device=device)
        return eng.run_stream(source, n_iters, chunk=chunk, depth=depth,
                              live=live, M=M)

    def warmup(self, linked: LinkedConfig, M: int, *,
               buckets: Optional[Sequence[int]] = None, lanes: int = 128,
               device=None, live=None) -> Dict[str, object]:
        eng = self.engine_for(linked, lanes=lanes, device=device)
        return eng.warmup(M, buckets, live=live)

    def stats(self) -> Dict[str, object]:
        """Aggregate over every engine: total traces / calls / samples,
        hit ratio, plus the per-engine breakdown."""
        with self._lock:
            engines = dict(self._engines)
        per = {}
        for (fp, lanes, placement), e in engines.items():
            name = f"{fp[:12]}/lanes={lanes}/{e.mode}"
            if placement is not None:
                name += f"/{placement}"
            per[name] = e.stats()
        traces = sum(e["traces"] for e in per.values())
        bucket_calls = sum(sum(e["bucket_calls"].values())
                           for e in per.values())
        hits = max(0, bucket_calls - traces)
        return {
            "engines": len(per),
            "traces": traces,
            "calls": sum(e["calls"] for e in per.values()),
            "samples": sum(e["samples"] for e in per.values()),
            "padded_samples": sum(e["padded_samples"] for e in per.values()),
            "streams": sum(e["streams"] for e in per.values()),
            "stream_chunks": sum(e["stream_chunks"] for e in per.values()),
            "transfer_words": sum(e["transfer_words"]
                                  for e in per.values()),
            "fabric_cycles": sum(e["fabric_cycles"] for e in per.values()),
            "mem_passes": sum(e["mem_passes"] for e in per.values()),
            "mem_chunks": sum(e["mem_chunks"] for e in per.values()),
            "hit_ratio": round(hits / bucket_calls, 4) if bucket_calls
            else None,
            "per_engine": per,
        }

    def __len__(self) -> int:
        with self._lock:
            return len(self._engines)


_default: Optional[CompiledKernelCache] = None
_default_lock = threading.Lock()


def default_engine() -> CompiledKernelCache:
    """The process-wide engine cache the pallas backend uses by default.
    Its aggregate stats are registered as the ``engine`` source in the
    metrics registry (``obs.registry().snapshot()["sources"]["engine"]``)
    — the source reads through this accessor, so swapping the default
    engine needs no re-registration."""
    global _default
    with _default_lock:
        if _default is None:
            _default = CompiledKernelCache()
            obs.registry().register_source(
                "engine", lambda: default_engine().stats(), replace=True)
        return _default


def set_default_engine(cache: Optional[CompiledKernelCache]
                       ) -> CompiledKernelCache:
    """Swap the process-wide engine cache (e.g. a fresh one in tests);
    returns the previous one so callers can restore it."""
    global _default
    prev = default_engine()
    with _default_lock:
        _default = cache
    return prev
