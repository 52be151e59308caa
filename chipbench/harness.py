"""One run of one cell: set-up, a measured window, the comparison with the
plain reference, and the result line.

A cell of ``BENCHMARK.json`` names a configuration (``configs/*.json``,
the deployment) and a traffic mix (``traffic/<mix>.json``).  The mix's
``loop`` picks the driver: ``closed`` streams a seeded pool through
``Executable.run_stream``; ``open`` offers single-sample requests to a
``ual.Service`` at fixed Poisson due times.  Per-layer metrics are
read, in a traced run, by the readers ``metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import generator, xtrace
from chipbench.stats import percentile

ROOT = Path(__file__).resolve().parents[1]
PKG = Path(__file__).resolve().parent
#: how long after the window closes the harness waits for an answer
GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, object]
    mix: Dict[str, object]
    end_to_end: List[Dict[str, object]]
    per_layer: List[Dict[str, object]]


def _applies(metric: Dict[str, object], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=generator.load_mix(w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(metric: str) -> Callable:
    """``metrics/<metric>.py``'s ``read(ctx)``."""
    path = PKG / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str):
    return importlib.import_module(f"chipbench.references.{name}")


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

def devices_for(chips: int, require_tpu: bool) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX platform is {devs[0].platform!r}, not a TPU")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def compile_config(config: Dict[str, object]):
    """Program, Target and Executable of the deployment, checked against
    the configuration's I/O spec."""
    from repro import ual
    program = ual.Program.from_kernel(
        config["kernel"], n_banks=int(config["n_banks"]),
        bank_words=int(config["bank_words"]))
    want = {**{k: int(v["length"]) for k, v in config["inputs"].items()},
            **{k: int(v) for k, v in config["outputs"].items()}}
    if dict(program.arrays) != want or \
            set(program.outputs) != set(config["outputs"]):
        raise ValueError(f"{config['name']}: program arrays "
                         f"{dict(program.arrays)} (outputs "
                         f"{program.outputs}) differ from the "
                         f"configuration's {want}")
    words = int(config["n_banks"]) * int(config["bank_words"])
    if program.layout.total_words != words:
        raise ValueError(f"scratchpad M={program.layout.total_words}, "
                         f"configured {words}")
    target = ual.Target.from_name(config["fabric"],
                                  backend=config["backend"],
                                  **config["fabric_args"])
    exe = ual.compile(program, target)
    if not exe.success:
        raise RuntimeError(f"{config['kernel']} does not map on "
                           f"{config['fabric']}")
    return program, target, exe


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _engine_stats() -> Dict[str, object]:
    from repro.ual.engine import default_engine
    return default_engine().stats()


class GcPauses:
    """Set-up's objects frozen out of the collector, so that a full
    collection in the window scans only what the window made (with JAX
    loaded, a full collection of everything stalls every thread for tens
    of milliseconds); the window's oldest-generation collections are
    timed for the log."""

    def __init__(self) -> None:
        self.pauses: List[float] = []
        self._t0 = 0.0

    def _cb(self, phase, info) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self) -> "GcPauses":
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._cb)
        gc.unfreeze()

    def summary(self) -> Dict[str, float]:
        return {"full_collections": len(self.pauses),
                "max_ms": 1e3 * max(self.pauses, default=0.0)}


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

class Bulk:
    """Closed loop: a seeded pool cycled through ``run_stream`` in full
    chunks until the window ends; a seeded sample of chunks is kept."""

    def __init__(self, cell: Cell, seed: int, devices: list) -> None:
        self.cell, self.seed = cell, seed
        mix, cfg = cell.mix, cell.config
        self.chunk = int(mix["chunk"])
        self.pool_n = int(mix["pool"])
        if self.pool_n % self.chunk:
            raise ValueError("the pool must hold whole chunks")
        self.every = int(mix["sample_every"])
        self.phase = int(generator.rng_for(seed, generator.STREAM_SAMPLE)
                         .integers(self.every))
        self.program, self.target, self.exe = compile_config(cfg)
        self.exe.warmup(tuple(mix["buckets"]))
        self.inputs = generator.make_inputs(cfg, seed, self.pool_n)
        self.pool = generator.as_requests(self.inputs)
        self.kept: List[Tuple[int, list]] = []
        self.summary: Dict[str, object] = {}

    def window(self, seconds: float, ann) -> Dict[str, float]:
        pool, n_pool, chunk = self.pool, self.pool_n, self.chunk
        t0 = time.perf_counter()
        t_end = t0 + seconds

        fed = [0]

        def feed():
            i = 0
            while i % chunk or time.perf_counter() < t_end:
                yield pool[i % n_pool]
                i += 1
                fed[0] = i

        gen = self.exe.run_stream(feed(), self.cell.config["n_iters"],
                                  chunk=chunk)
        n_out, j = 0, 0
        with ann(xtrace.WINDOW):
            while True:
                with ann("stream-step"):
                    outs = next(gen, None)
                if outs is None:
                    break
                with ann("drain"):
                    if j % self.every == self.phase:
                        self.kept.append((j, outs))
                    n_out += len(outs)
                    j += 1
            t1 = time.perf_counter()
        self.summary = dict(self.exe.last_info)
        self.attempted = fed[0]
        self.n_out = n_out
        self.t0, self.t1 = t0, t1
        return {"samples_per_s": n_out / (t1 - t0)}

    def answers(self) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray],
                               int]:
        """(inputs, produced outputs) of the kept chunks, and how many
        answers never came."""
        idx, got = [], {k: [] for k in self.cell.config["outputs"]}
        missing = max(0, self.attempted - self.n_out)
        for j, outs in self.kept:
            rows = [(j * self.chunk + r) % self.pool_n
                    for r in range(self.chunk)]
            missing += max(0, self.chunk - len(outs))
            for r, out in zip(rows, outs):
                idx.append(r)
                for k in got:
                    got[k].append(out[k])
        idx = np.asarray(idx, np.int64)
        inputs = {k: v[idx] for k, v in self.inputs.items()}
        produced = {k: np.asarray(v, np.int32).reshape(len(idx), -1)
                    for k, v in got.items()}
        return inputs, produced, missing

    def counters(self) -> Dict[str, object]:
        return {"engine": _engine_stats()}

    def close(self) -> None:
        pass


class Open:
    """Open loop: single-sample requests offered to a ``ual.Service`` at
    seeded Poisson due times; latency runs from each due time to the
    moment the answer resolves."""

    def __init__(self, cell: Cell, seed: int, devices: list) -> None:
        from repro import ual
        from repro.ual.backends import get_backend
        self.cell, self.seed = cell, seed
        mix, cfg = cell.mix, cell.config
        self.program, self.target, self.exe = compile_config(cfg)
        buckets = tuple(mix["buckets"])
        replicas = int(cfg["replicas"])
        if replicas > 1:
            # Service warms only the default-device engine: warm every
            # replica's device-pinned engine here, before the window
            be = get_backend(cfg["backend"])
            for dev in devices[:replicas]:
                be.warmup(self.program, self.exe.map_result,
                          lowered=self.exe.lowered, buckets=buckets,
                          device=dev)
        self.svc = ual.Service(
            max_batch=int(cfg["service"]["max_batch"]),
            replicas=replicas,
            devices=devices[:replicas] if replicas > 1 else None,
            warmup_buckets=buckets)
        self.pool_n = int(mix["pool"])
        self.inputs = generator.make_inputs(cfg, seed, self.pool_n)
        self.pool = generator.as_requests(self.inputs)
        self.rate = float(mix["rate_per_s"])
        # the class compiles and warms on its first requests: a burst of
        # one full batch per replica, answered before the window
        try:
            warm = [self.svc.submit(self.program, self.target,
                                    self.pool[i % self.pool_n],
                                    tenant=cfg["name"])
                    for i in range(int(cfg["service"]["max_batch"])
                                   * replicas)]
            for f in warm:
                f.result(timeout=600)
        except BaseException:
            self.svc.shutdown()
            raise

    def window(self, seconds: float, ann, rate: Optional[float] = None
               ) -> Dict[str, float]:
        rate = self.rate if rate is None else rate
        due = generator.arrivals({"rate_per_s": rate}, self.seed, seconds)
        n = len(due)
        done = np.full(n, np.nan)
        late = np.zeros(n)
        futs: list = [None] * n
        svc, program, target = self.svc, self.program, self.target
        pool, n_pool, tenant = self.pool, self.pool_n, self.cell.config["name"]
        perf, sleep = time.perf_counter, time.sleep

        def stamp(i):
            return lambda _f: done.__setitem__(i, perf())

        t0 = perf()
        due_abs = t0 + due
        with ann(xtrace.WINDOW):
            for i in range(n):
                wait = due_abs[i] - perf()
                if wait > 0:
                    with ann("generate"):
                        sleep(wait)
                with ann("submit"):
                    late[i] = perf() - due_abs[i]
                    f = svc.submit(program, target, pool[i % n_pool],
                                   tenant=tenant)
                    f.add_done_callback(stamp(i))
                futs[i] = f
            t_close = t0 + seconds
            if perf() < t_close:
                with ann("generate"):
                    sleep(t_close - perf())
        with ann("drain"):
            give_up = t_close + GRACE_S
            for f in futs:
                with contextlib.suppress(TimeoutError):
                    f.exception(timeout=max(0.0, give_up - perf()))
        self.t0, self.t_close = t0, t_close
        self.due_abs, self.done, self.futs, self.late = due_abs, done, futs, late
        lat = np.where(np.isnan(done), np.inf, done - due_abs)
        failed = np.array([not f.done() or f.exception(0) is not None
                           for f in futs], bool)
        lat[failed] = np.inf
        self.failed_mask = failed
        cap_ms = (seconds + GRACE_S) * 1e3
        res = {}
        for name, q in (("p50_ms", 50), ("p95_ms", 95)):
            v = percentile(list(lat * 1e3), q) if n else math.inf
            res[name] = v if math.isfinite(v) else cap_ms
        return res

    def lateness(self) -> Dict[str, float]:
        if not len(self.late):
            return {}
        ms = self.late * 1e3
        return {"late_p50_ms": float(np.percentile(ms, 50)),
                "late_p99_ms": float(np.percentile(ms, 99)),
                "late_max_ms": float(ms.max()),
                "late_over_10ms": int((ms > 10.0).sum())}

    def answers(self):
        n = len(self.futs)
        idx = np.arange(n) % self.pool_n
        ok = [i for i in range(n) if not self.failed_mask[i]]
        # never answered, or answered with an error (a rejection too)
        missing = int(self.failed_mask.sum())
        inputs = {k: v[idx[ok]] for k, v in self.inputs.items()}
        outs = [self.futs[i].result(0) for i in ok]
        produced = {k: np.asarray([o[k] for o in outs],
                                  np.int32).reshape(len(ok), -1)
                    for k in self.cell.config["outputs"]}
        return inputs, produced, missing

    @property
    def attempted(self) -> int:
        return len(self.futs)

    def counters(self) -> Dict[str, object]:
        return {"service": self.svc.stats()}

    def close(self) -> None:
        self.svc.shutdown()


DRIVERS = {"closed": Bulk, "open": Open}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def compare(config: Dict[str, object], inputs: Dict[str, np.ndarray],
            produced: Dict[str, np.ndarray], missing: int,
            control: bool = False) -> Dict[str, Dict[str, int]]:
    """Every produced answer against the plain reference, bit for bit.

    With ``control`` the answers are replaced by the reference computed
    on a 16-bit datapath (the nearest width below the configured int32):
    the comparison has to reject them."""
    ref = load_reference(config["reference"])
    n_iters = int(config["n_iters"])
    want = ref.run(inputs, n_iters, bits=int(config["datapath_bits"]))
    if control:
        produced = ref.run(inputs, n_iters, bits=16)
    n = len(next(iter(inputs.values()))) if inputs else 0
    wrong = np.zeros(n, bool)
    for k in config["outputs"]:
        got = produced[k]
        if got.shape != want[k].shape:
            wrong[:] = True
        else:
            wrong |= (got != want[k]).any(axis=1)
    return {"wrong_answers": {"value": int(wrong.sum()), "limit": 0},
            "missing_answers": {"value": int(missing), "limit": 0},
            "answers_compared": {"value": int(n), "min": 1}}


def checks_pass(checks: Dict[str, Dict[str, int]]) -> bool:
    return all(("limit" not in c or c["value"] <= c["limit"])
               and ("min" not in c or c["value"] >= c["min"])
               for c in checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Cell
    driver: object
    trace: Optional[xtrace.Trace]
    spans: list
    before: Dict[str, object]
    after: Dict[str, object]
    peaks: Dict[str, Optional[float]]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, control: bool = False,
             require_tpu: bool = True, log=print) -> Dict[str, object]:
    """Set up ``cell``, run its window for ``seconds`` and compare; the
    result line as a dict.  ``require_tpu=False`` lets the tests drive
    every step but the look for a chip on the CPU."""
    from repro.ual.engine import CompiledKernelCache, set_default_engine

    from chipbench.peaks import peaks_for

    devices = devices_for(cell.chips, require_tpu)
    peaks = peaks_for(devices[0].device_kind) if require_tpu else {}
    prev_engine = set_default_engine(CompiledKernelCache())
    try:
        return _run(cell, seed, seconds, trace, t_start, control, devices,
                    peaks, log)
    finally:
        set_default_engine(prev_engine)


def _run(cell, seed, seconds, trace, t_start, control, devices, peaks,
         log) -> Dict[str, object]:
    import jax

    from repro import obs

    kind = devices[0].device_kind
    driver = DRIVERS[cell.mix["loop"]](cell, seed, devices)
    try:
        traces0 = _engine_stats()["traces"]
        before = driver.counters()
        tracer = obs.tracer()
        trace_dir = ROOT / "artifacts" / "chipbench" / "trace" / cell.name
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracer.clear()
            tracer.enable()
        with GcPauses() as pauses:
            setup_s = time.perf_counter() - t_start
            e2e = driver.window(seconds, _annotate(trace))
        if trace:
            tracer.disable()
            jax.profiler.stop_trace()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        compiles = _engine_stats()["traces"] - traces0
        after = driver.counters()
        spans = tracer.spans() if trace else []
        if hasattr(driver, "lateness"):
            log("generator lateness: " + json.dumps(driver.lateness()))
        log(f"compiles in the window: {compiles}")
        log("gc in the window: " + json.dumps(pauses.summary()))
    finally:
        driver.close()

    inputs, produced, missing = driver.answers()
    checks = compare(cell.config, inputs, produced, missing, control)
    attempted = int(driver.attempted)
    failed = missing + checks["wrong_answers"]["value"]

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result: Dict[str, object] = {"correct": checks_pass(checks),
                                 "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = {**e2e, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        xt = None
        path = xtrace.find_xplane(str(trace_dir))
        if path is not None:
            xt = xtrace.load(path)
            if xt.window is None:
                xt = None
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(cell, driver, xt, spans, before, after, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        if xt is not None and xt.active_devices():
            device["busy_s"] = xt.busy_s()
            device["window_s"] = xt.window_s
            result["breakdown"] = {"device_ops": xt.top_ops(),
                                   "idle_gaps": xt.idle_by_host_span()}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result
