"""The sharded serving cluster: replicas, routing, stealing, processes.

Contract under test:

  * ``Router`` routes flush-ready micro-batches to the least-loaded
    replica slot (class-affinity tiebreak), an idle slot steals the
    oldest batch from the most-loaded sibling, and ``stop()`` drains
    queues before workers exit,
  * ``Coalescer.steal_oldest`` honors the minimum bucket age (idle
    capacity never flushes a brand-new bucket) and pops earliest-due,
  * ``Service(replicas=N)`` keeps oracle parity through the replicated
    path, reports the router in ``stats()``, and flushes partial buckets
    early when replicas idle,
  * the sharded engine path (``pallas_sharded``) is bit-exact vs the
    interpreter oracle, including a ragged final chunk, both in-process
    and in a fresh process with 2 forced host devices,
  * a cold class compiled by several *processes* against one shared
    disk cache pays exactly ONE mapping cluster-wide (the cross-process
    per-key lock),
  * ``MappingCache`` disk writes are atomic and tolerate a concurrent
    writer winning the ``os.replace`` race,
  * ``ClusterService`` resolves parent-side futures bit-exact through
    worker processes and merges their stats into one cluster view,
  * self-healing: a worker killed mid-batch (deterministic
    ``FaultPlan``) strands no future — orphaned requests retry
    transparently on live workers with bit-exact results, the dead
    worker respawns under the ``RestartPolicy`` and rejoins warm off
    the shared disk cache; with the retry/restart budgets at zero the
    caller gets a ``worker-died`` verdict instead; shutdown racing a
    respawn leaks no process,
  * a short soak keeps queue depth bounded and p99 finite.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import ual
from repro.core.dfg import interpret
from repro.launch.mesh import forced_device_env
from repro.ual.cluster.replica import Router
from repro.ual.service.coalescer import Coalescer
from repro.ual.service.queue import Request

REPO = Path(__file__).resolve().parents[1]


def _program(kname="gemm"):
    return ual.Program.from_kernel(kname)


def _target(**knobs):
    return ual.Target.from_name("hycube", rows=4, cols=4, **knobs)


def _oracle(program, mem):
    return interpret(program.dfg, mem, program.n_iters)


# ---------------------------------------------------------------------------
# Router units
# ---------------------------------------------------------------------------

def test_router_routes_least_loaded_under_skew():
    r = Router(3)
    r.slots[0].in_flight = 2     # busy
    r.slots[1].in_flight = 1
    idx = r.route("k", ["b0"])
    assert idx == 2              # the empty slot
    # slot 2 now has 1 queued == slot 1's in-flight; next goes to 1 or 2,
    # never to the most-loaded slot 0
    assert r.route("k", ["b1"]) != 0
    assert r.stats()["decisions"]["least_loaded"] == 2


def test_router_affinity_breaks_ties_toward_warm_slot():
    r = Router(3)
    r.slots[2].warm.add("classA")
    assert r.route("classA", ["b"]) == 2
    assert r.stats()["decisions"]["affinity"] == 1
    # a colder class at equal load ignores warmth it doesn't have
    assert r.route("classB", ["b"]) != 2


def test_router_idle_pull_steals_oldest_from_most_loaded():
    r = Router(2)
    r.route("k", ["old"])        # both land on slot 0: it is least-loaded
    r.route("k", ["new"])        # only until its queue grows — but route
    # load counts queued batches, so the second goes to slot 1; force the
    # skew the scheduler would see under a burst instead:
    r.slots[0].queue.extend(r.slots[1].queue)
    r.slots[1].queue.clear()
    key, batch, stolen = r.pull(1, timeout=0.1)
    assert stolen and batch == ["old"]     # FIFO across the pool
    assert r.slots[1].steals == 1 and r.stats()["steals"] == 1
    r.done(1, 1, 0.01)
    assert r.slots[1].samples == 1


def test_router_stop_drains_queues_before_none():
    r = Router(1)
    r.route("k", ["pending"])
    r.stop()
    item = r.pull(0, timeout=1.0)
    assert item is not None and item[1] == ["pending"]
    r.done(0, 1, 0.0)
    assert r.pull(0, timeout=1.0) is None


def test_router_validates_inputs():
    with pytest.raises(ValueError):
        Router(0)
    with pytest.raises(ValueError):
        Router(3, devices=[None, None])


# ---------------------------------------------------------------------------
# Coalescer stealing
# ---------------------------------------------------------------------------

def test_coalescer_steal_oldest_honors_min_age():
    c = Coalescer(max_batch=8, max_wait_s=1.0)
    program, target = _program(), _target()
    r1 = Request(tenant="a", program=program, target=target, mem={},
                 n_iters=4, t_submit=100.0)
    r2 = Request(tenant="b", program=program, target=target, mem={},
                 n_iters=8, t_submit=100.5)       # different class
    c.offer(r1)
    c.offer(r2)
    assert c.steal_oldest(100.05, min_age_s=0.1) is None   # too young
    got = c.steal_oldest(100.2, min_age_s=0.1)             # r1 aged enough
    assert got == [r1]                                     # earliest-due
    assert c.pending() == 1
    assert c.steal_oldest(100.55, min_age_s=0.1) is None   # r2 still young
    assert c.steal_oldest(100.7, min_age_s=0.1) == [r2]


# ---------------------------------------------------------------------------
# Service in replicated mode (sim backend)
# ---------------------------------------------------------------------------

def test_replicated_service_parity_and_router_stats():
    program, target = _program(), _target()
    rng = np.random.default_rng(1)
    mems = [program.random_inputs(rng) for _ in range(24)]
    with ual.Service(max_batch=8, max_wait_ms=30, replicas=2) as svc:
        resps = [svc.submit(program, target, m) for m in mems]
        outs = [r.result(timeout=300) for r in resps]
        stats = svc.stats()
    for mem, out in zip(mems, outs):
        expect = _oracle(program, mem)
        for name in program.outputs:
            np.testing.assert_array_equal(out[name], expect[name])
    router = stats["router"]
    assert router["replicas"] == 2
    assert len(router["slots"]) == 2
    assert sum(s["samples"] for s in router["slots"]) == 24
    assert sum(router["decisions"].values()) == \
        sum(s["batches"] for s in router["slots"])
    for slot in router["slots"]:
        for k in ("batches", "samples", "busy_s", "samples_per_s",
                  "steals", "warm_classes"):
            assert k in slot


def test_replicated_service_early_flush_when_replicas_idle():
    """With a long age limit and idle replicas, partial buckets flush
    early (coalescer-side stealing) instead of waiting out the clock."""
    program, target = _program(), _target()
    mem = program.random_inputs(np.random.default_rng(2))
    with ual.Service(max_batch=64, max_wait_ms=2000, replicas=2) as svc:
        t0 = time.perf_counter()
        resp = svc.submit(program, target, mem)
        resp.result(timeout=300)
        waited = time.perf_counter() - t0
        stats = svc.stats()
    assert waited < 1.5, "early flush should beat the 2s age limit"
    assert stats["router"]["early_flushes"] >= 1


# ---------------------------------------------------------------------------
# sharded engine path
# ---------------------------------------------------------------------------

def test_sharded_backend_parity_including_ragged_batch():
    """pallas_sharded == interp oracle on whatever mesh this host has
    (1 device in-process), including a batch that is ragged vs the
    device count and bucket ladder."""
    program, target = _program(), _target(backend="pallas")
    exe = ual.compile(program, target)
    rng = np.random.default_rng(3)
    mems = [program.random_inputs(rng) for _ in range(5)]
    outs = exe.run_batch(mems, backend="pallas_sharded")
    for mem, out in zip(mems, outs):
        expect = _oracle(program, mem)
        for name in program.outputs:
            np.testing.assert_array_equal(out[name], expect[name])
    assert exe.last_info["engine"] == "pallas-jit-sharded"
    assert exe.last_info["n_devices"] >= 1


def test_sharded_parity_under_forced_two_devices():
    """A fresh process with 2 forced host devices runs the sharded path
    bit-exact, with the batch axis genuinely split over both."""
    code = (
        "from repro.launch.mesh import forced_host_devices\n"
        "forced_host_devices(2)\n"
        "import numpy as np\n"
        "from repro import ual\n"
        "from repro.core.dfg import interpret\n"
        "import jax\n"
        "assert len(jax.devices()) == 2\n"
        "program = ual.Program.from_kernel('gemm')\n"
        "target = ual.Target.from_name('hycube', rows=4, cols=4,\n"
        "                              backend='pallas')\n"
        "exe = ual.compile(program, target)\n"
        "rng = np.random.default_rng(0)\n"
        "mems = [program.random_inputs(rng) for _ in range(5)]\n"
        "outs = exe.run_batch(mems, backend='pallas_sharded')\n"
        "ok = all(np.array_equal(\n"
        "    o[n], interpret(program.dfg, m, program.n_iters)[n])\n"
        "    for m, o in zip(mems, outs) for n in program.outputs)\n"
        "print('DEVICES', exe.last_info['n_devices'], 'PARITY', ok)\n"
    )
    env = forced_device_env(2)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=str(REPO), timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DEVICES 2 PARITY True" in out.stdout


# ---------------------------------------------------------------------------
# cross-process compile-once through the shared disk cache
# ---------------------------------------------------------------------------

def test_cold_compile_happens_once_across_processes(tmp_path):
    """Three processes race one cold class against a shared disk cache:
    the cross-process per-key lock makes exactly one pay the mapping;
    the others block briefly and load the artifact."""
    code = (
        "import sys\n"
        "from repro import ual\n"
        "cache = ual.MappingCache(disk_dir=sys.argv[1])\n"
        "program = ual.Program.from_kernel('gemm')\n"
        "target = ual.Target.from_name('hycube', rows=4, cols=4)\n"
        "exe = ual.compile(program, target, cache=cache)\n"
        "rec = {p.name: p.stats for p in exe.compile_info.passes}\n"
        "print('MAPPING', rec['mapping'].get('cache'))\n"
    )
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    procs = [subprocess.Popen([sys.executable, "-c", code, str(tmp_path)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=str(REPO))
             for _ in range(3)]
    outs = [p.communicate(timeout=560) for p in procs]
    for p, (stdout, stderr) in zip(procs, outs):
        assert p.returncode == 0, stderr[-2000:]
    verdicts = [stdout.strip().split()[-1] for stdout, _ in outs]
    assert verdicts.count("miss") == 1, verdicts
    assert verdicts.count("hit") == 2, verdicts
    mapping_pkls = [f for f in tmp_path.glob("*.pkl")
                    if not f.name.endswith("_low.pkl")]
    assert len(mapping_pkls) == 1


def test_write_atomic_tolerates_concurrent_winner(tmp_path, monkeypatch):
    """If ``os.replace`` fails but another writer already installed the
    entry, the write is a success (the artifact is there); if nobody
    installed it, the failure surfaces."""
    cache = ual.MappingCache(disk_dir=tmp_path)
    path = tmp_path / "entry.pkl"

    real_replace = os.replace

    def losing_replace(src, dst):
        real_replace(src, dst)      # "the other writer" wins first...
        raise OSError("simulated lost rename race")

    monkeypatch.setattr(os, "replace", losing_replace)
    cache._write_atomic(path, {"payload": 1})       # tolerated
    assert path.exists()
    assert not list(tmp_path.glob("*.tmp.*")), "tmp files must be cleaned"

    def failing_replace(src, dst):
        raise OSError("disk detached")

    gone = tmp_path / "never.pkl"
    monkeypatch.setattr(os, "replace", failing_replace)
    with pytest.raises(OSError):
        cache._write_atomic(gone, {"payload": 2})
    assert not gone.exists()
    assert not list(tmp_path.glob("*.tmp.*"))


def test_process_lock_key_is_reentrant_across_instances(tmp_path):
    """Two cache instances over one directory serialize on the same
    per-key lock file (the in-process analogue of the subprocess race)."""
    a = ual.MappingCache(disk_dir=tmp_path)
    b = ual.MappingCache(disk_dir=tmp_path)
    key = ("p" * 24, "t" * 24)
    la = a.process_lock_key(key)
    lb = b.process_lock_key(key)
    assert la is not None and lb is not None
    assert Path(la._path) == Path(lb._path)
    with la:
        assert Path(la._path).exists()
    with lb:
        pass
    assert ual.MappingCache(disk_dir=None).process_lock_key(key) is None


# ---------------------------------------------------------------------------
# ClusterService end-to-end (worker processes, sim backend)
# ---------------------------------------------------------------------------

def test_cluster_service_parity_and_merged_stats(tmp_path):
    program, target = _program(), _target()
    rng = np.random.default_rng(4)
    mems = [program.random_inputs(rng) for _ in range(16)]
    with ual.ClusterService(workers=2, max_batch=8, max_wait_ms=10,
                            cache_dir=str(tmp_path)) as cs:
        resps = [cs.submit(program, target, m) for m in mems]
        outs = [r.result(timeout=300) for r in resps]
        stats = cs.stats()
    for mem, out in zip(mems, outs):
        expect = _oracle(program, mem)
        for name in program.outputs:
            np.testing.assert_array_equal(out[name], expect[name])
    # every response knows which worker ran it
    assert all(r.info.get("worker") in (0, 1) for r in resps)
    # merged cluster schema
    assert stats["cluster"] is True and stats["workers"] == 2
    assert stats["completed"] == 16 and stats["rejected"] == 0
    assert stats["samples_per_s"] > 0 and stats["p99_ms"] is not None
    assert set(stats["routing"]["decisions"]) == {"affinity",
                                                  "least_loaded", "retry"}
    assert stats["routing"]["decisions"]["retry"] == 0  # no deaths here
    assert sum(stats["routing"]["decisions"].values()) == 16
    assert sorted(stats["per_worker"]) == [0, 1]
    for snap in stats["per_worker"].values():
        for k in ("completed", "p50_ms", "p99_ms", "samples_per_s",
                  "cache", "engine"):
            assert k in snap


def test_cluster_service_rejects_after_shutdown(tmp_path):
    program, target = _program(), _target()
    cs = ual.ClusterService(workers=1, max_batch=4, max_wait_ms=5,
                            cache_dir=str(tmp_path))
    cs.shutdown()
    resp = cs.submit(program, target,
                     program.random_inputs(np.random.default_rng(5)))
    assert resp.rejected and resp.reason == "shutdown"


# ---------------------------------------------------------------------------
# self-healing: kill/retry/respawn/warm-rejoin (deterministic fault plans)
# ---------------------------------------------------------------------------

def _wait_respawn(cs, widx, timeout=60.0):
    """Poll supervision until worker ``widx`` is alive again post-restart;
    returns its final supervision snapshot."""
    deadline = time.time() + timeout
    snap = None
    while time.time() < deadline:
        snap = cs.stats(timeout=30)["supervision"]["workers"][widx]
        if snap["restarts"] >= 1 and snap["alive"]:
            return snap
        time.sleep(0.2)
    raise AssertionError(f"worker {widx} never respawned: {snap}")


def test_cluster_kill_midbatch_transparent_retry(tmp_path):
    """Worker 0 is killed (hard exit, no goodbye) with requests in
    flight: every future still resolves bit-exact — orphans ride retry
    hops to worker 1 — and worker 0 respawns under the policy."""
    program, target = _program(), _target()
    rng = np.random.default_rng(7)
    mems = [program.random_inputs(rng) for _ in range(24)]
    plan = ual.FaultPlan([ual.FaultSpec("kill_worker", worker=0, after=3)])
    with ual.ClusterService(
            workers=2, max_batch=8, max_wait_ms=2, cache_dir=str(tmp_path),
            worker_env=plan.to_env(),
            restart_policy=ual.RestartPolicy(max_restarts=2,
                                             backoff_base_s=0.1)) as cs:
        resps = [cs.submit(program, target, m) for m in mems]
        outs = [r.result(timeout=300) for r in resps]    # nothing lost
        for mem, out in zip(mems, outs):
            expect = _oracle(program, mem)
            for name in program.outputs:
                np.testing.assert_array_equal(out[name], expect[name])
        assert any(r.info.get("retries", 0) >= 1 for r in resps), \
            "the kill must strand (and retry) at least one request"
        assert all(r.info.get("retries", 0) <= cs.max_retries
                   for r in resps)
        snap = _wait_respawn(cs, 0)
        stats = cs.stats(timeout=30)
    assert snap["deaths"] == 1 and snap["restarts"] == 1
    assert snap["last_recovery_s"] is not None
    sup = stats["supervision"]
    assert sup["restarts_total"] == 1 and sup["deaths_total"] == 1
    assert sup["retries_total"] == stats["routing"]["decisions"]["retry"] >= 1
    assert sup["policy"]["max_restarts"] == 2


def test_cluster_retry_exhaustion_yields_worker_died_verdict(tmp_path):
    """Budgets at zero: the stranded request resolves with a
    ``worker-died`` verdict (never hangs), and with no live worker left
    later submits are rejected up front."""
    program, target = _program(), _target()
    mem = program.random_inputs(np.random.default_rng(8))
    plan = ual.FaultPlan([ual.FaultSpec("kill_worker", worker=0)])
    with ual.ClusterService(
            workers=1, max_batch=4, max_wait_ms=2, cache_dir=str(tmp_path),
            worker_env=plan.to_env(), max_retries=0,
            restart_policy=ual.RestartPolicy(max_restarts=0)) as cs:
        resp = cs.submit(program, target, mem)   # its arrival is the kill
        with pytest.raises(ual.ServiceRejected) as err:
            resp.result(timeout=120)
        assert err.value.reason == "worker-died"
        assert resp.info.get("retries") == 0
        deadline = time.time() + 60
        while cs.stats(timeout=10)["supervision"]["workers"][0]["alive"]:
            assert time.time() < deadline, "death never detected"
            time.sleep(0.1)
        late = cs.submit(program, target, mem)
        assert late.rejected and late.reason == "worker-died"
        sup = cs.stats(timeout=10)["supervision"]
    assert sup["workers"][0]["exhausted"] is True
    assert sup["restarts_total"] == 0


def test_cluster_respawned_worker_rejoins_warm(tmp_path):
    """A respawned worker re-registers its classes and re-loads
    artifacts from the shared disk cache: it serves again with ZERO
    fresh mapping stores (disk hits only)."""
    program, target = _program(), _target()
    rng = np.random.default_rng(9)
    mems = [program.random_inputs(rng) for _ in range(8)]
    plan = ual.FaultPlan([ual.FaultSpec("kill_worker", worker=0, after=2)])
    with ual.ClusterService(
            workers=2, max_batch=4, max_wait_ms=2, cache_dir=str(tmp_path),
            worker_env=plan.to_env(),
            restart_policy=ual.RestartPolicy(max_restarts=1,
                                             backoff_base_s=0.1)) as cs:
        for r in [cs.submit(program, target, m) for m in mems]:
            r.result(timeout=300)
        _wait_respawn(cs, 0)
        # sequential requests route to the warm-affine least-loaded
        # worker 0; stay under the re-armed kill threshold (after=2)
        outs = []
        for mem in mems[:2]:
            outs.append(cs.submit(program, target, mem).result(timeout=300))
        for mem, out in zip(mems[:2], outs):
            expect = _oracle(program, mem)
            for name in program.outputs:
                np.testing.assert_array_equal(out[name], expect[name])
        stats = cs.stats(timeout=30)
    w0 = stats["per_worker"].get(0)
    assert w0 is not None, "respawned worker must answer stats"
    mapping = w0["cache"]["mapping"]
    assert mapping["stores"] == 0, "warm rejoin must not re-map"
    assert mapping["disk_hits"] >= 1, "artifacts must come off shared disk"


def test_cluster_shutdown_during_respawn_leaks_nothing(tmp_path):
    """Shutdown racing the respawn window: the watchdog either installs
    the replacement (then it is stopped like any worker) or reaps it —
    no leaked process, no wedged watchdog thread."""
    program, target = _program(), _target()
    mem = program.random_inputs(np.random.default_rng(10))
    plan = ual.FaultPlan([ual.FaultSpec("kill_worker", worker=0)])
    cs = ual.ClusterService(
        workers=1, max_batch=4, max_wait_ms=2, cache_dir=str(tmp_path),
        worker_env=plan.to_env(),
        restart_policy=ual.RestartPolicy(max_restarts=3,
                                         backoff_base_s=0.05))
    resp = cs.submit(program, target, mem)       # kills the only worker
    deadline = time.time() + 60
    while cs.stats(timeout=10)["supervision"]["workers"][0]["deaths"] < 1:
        assert time.time() < deadline, "death never detected"
        time.sleep(0.05)
    cs.shutdown()                                # races the respawn
    assert all(not p.is_alive() for p in cs._procs), "leaked worker"
    assert all(not t.is_alive() for t in cs._threads), "wedged thread"
    with pytest.raises(ual.ServiceRejected):     # resolved, not stuck
        resp.result(timeout=5)


# ---------------------------------------------------------------------------
# soak: bounded depth, finite tail
# ---------------------------------------------------------------------------

def test_replicated_soak_bounded_queue_and_finite_p99():
    """A short steady load through the replicated service: queue depth
    stays bounded (admission control works) and p99 is finite."""
    program, target = _program(), _target()
    rng = np.random.default_rng(6)
    mems = [program.random_inputs(rng) for _ in range(8)]
    depths = []
    with ual.Service(max_batch=8, max_wait_ms=5, max_queue=64,
                     replicas=2) as svc:
        resps = []
        t_end = time.perf_counter() + 2.0
        while time.perf_counter() < t_end:
            resps.append(svc.submit(program, target, mems[len(resps) % 8]))
            depths.append(svc.stats()["queue_depth"])
            time.sleep(0.01)
        completed = 0
        for r in resps:
            try:
                r.result(timeout=300)
                completed += 1
            except ual.ServiceRejected:
                pass            # bounded-queue rejection is the contract
        stats = svc.stats()
    assert max(depths) <= 64, "queue depth must stay bounded"
    assert stats["p99_ms"] is not None and np.isfinite(stats["p99_ms"])
    assert stats["completed"] == completed > 0


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_router_pins_slots_to_chips_on_tpu(monkeypatch):
    """On a TPU, replicas without ``devices=`` go one per chip
    (``jax.devices()[i]``); more replicas than chips is an error."""
    import jax

    import repro.kernels.cgra_exec.kernel as kernel
    monkeypatch.setattr(kernel, "interpret_mode", lambda: False)
    n = len(jax.devices())
    router = Router(n)
    assert [s.device for s in router.slots] == jax.devices()[:n]
    with pytest.raises(ValueError, match="devices"):
        Router(n + 1)
    monkeypatch.setattr(kernel, "interpret_mode", lambda: True)
    assert [s.device for s in Router(2).slots] == [None, None]


@pytest.mark.parametrize("backend", ["pallas", "pallas_sharded"])
def test_cluster_service_refuses_device_backend_on_tpu(monkeypatch,
                                                       backend):
    """Spawned workers cannot share a chip: on a TPU the cluster front
    end refuses device backends and points at Service(replicas=...)."""
    from repro.ual.cluster import service as cluster
    monkeypatch.setattr(cluster, "_tpu_platform", lambda: True)
    svc = ual.ClusterService(workers=1, start=False)
    program = _program()
    mem = program.random_inputs(np.random.default_rng(0))
    with pytest.raises(ValueError, match=r"Service\(replicas="):
        svc.submit(program, _target(backend=backend), mem)
    cluster._check_backend_off_chip("sim")          # host backends pass


def test_tpu_platform_read_without_starting_jax():
    """The cluster front end asks for the platform without importing JAX
    (a fresh process: importing the module must not pull JAX in)."""
    code = ("import os, sys\n"
            "from repro.ual.cluster.service import _tpu_platform\n"
            "out = []\n"
            "for plats in ('cpu', 'tpu', 'tpu,cpu'):\n"
            "    os.environ['JAX_PLATFORMS'] = plats\n"
            "    out.append(_tpu_platform())\n"
            "print('jax' in sys.modules, out)\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.strip()
    assert out == "False [False, True, True]"
