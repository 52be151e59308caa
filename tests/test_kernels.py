"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Each kernel is swept over shapes and dtypes and asserted allclose against
its ref.py oracle; cgra_exec is additionally checked BIT-EXACTLY against
the cycle-accurate simulator for every paper benchmark kernel on both the
HyCUBE and N2N fabrics (the Morpher validation flow, Table II).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ops import ssd_op
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.rwkv6.ops import wkv6_op
from repro.kernels.rwkv6.ref import wkv6_ref

TOL = {jnp.float32: 2e-3, jnp.bfloat16: 5e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (1, 128, 128, 4, 4, 64),       # MHA, square
    (2, 64, 256, 8, 2, 32),        # GQA 4:1, cross lengths
    (1, 200, 200, 4, 1, 64),       # MQA, non-multiple of block
    (1, 32, 512, 4, 4, 128),       # long KV
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, Sq, Skv, H, KV, D, causal, window):
    if causal and Sq != Skv:
        pytest.skip("causal requires square for this oracle")
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), jnp.float32)
    got = flash_attention_op(q, k, v, causal=causal, window=window,
                             bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (2, 128, 4, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (2, 128, 4, 64)).astype(dtype)
    got = flash_attention_op(q, k, v, interpret=True).astype(jnp.float32)
    want = attention_ref(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=_tol(dtype), rtol=_tol(dtype))


# ---------------------------------------------------------------------------
# rwkv6 chunked WKV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,K,chunk", [
    (1, 32, 2, 8, 16),
    (2, 70, 3, 16, 32),            # ragged final chunk
    (1, 128, 1, 64, 32),
    (2, 33, 4, 8, 32),             # single ragged chunk
])
def test_wkv6_sweep(B, S, H, K, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, K), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, K), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, K), jnp.float32)
    lw = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], (B, S, H, K))), -8.0)
    u = jax.random.normal(ks[4], (H, K))
    got = wkv6_op(r, k, v, lw, u, chunk=chunk, interpret=True)
    want = wkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = jax.random.normal(ks[0], (1, 64, 2, 16)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 16)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 16)).astype(dtype)
    lw = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], (1, 64, 2, 16))),
                     -8.0).astype(dtype)
    u = jax.random.normal(ks[4], (2, 16)).astype(dtype)
    got = wkv6_op(r, k, v, lw, u, interpret=True).astype(jnp.float32)
    want = wkv6_ref(r, k, v, lw, u).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=_tol(dtype), rtol=5e-2)


def test_wkv6_matches_model_chunked():
    """The model's pure-jnp chunked path == the kernel (same algorithm)."""
    from repro.models.rwkv6 import wkv6_chunked
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    shape = (2, 48, 2, 8)
    r, k, v = (jax.random.normal(ks[i], shape, jnp.float32) for i in range(3))
    lw = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], shape)), -8.0)
    u = jax.random.normal(ks[4], (2, 8))
    got = wkv6_op(r, k, v, lw, u, chunk=32, interpret=True)
    want = wkv6_chunked(r, k, v, lw, u, chunk=32)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# mamba2 SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 8, 8, 16),
    (2, 70, 3, 8, 12, 32),         # ragged final chunk
    (1, 128, 2, 16, 16, 64),
])
def test_ssd_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A_log = jax.random.normal(ks[4], (H,)) * 0.5
    D = jax.random.normal(ks[5], (H,))
    got = ssd_op(x, dt, A_log, Bm, Cm, D, chunk=chunk, interpret=True)
    want = ssd_ref(x, dt, A_log, Bm, Cm, D)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_ssd_matches_model_chunked():
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (2, 48, 2, 8), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 48, 2)))
    Bm = jax.random.normal(ks[2], (2, 48, 8))
    Cm = jax.random.normal(ks[3], (2, 48, 8))
    A_log = jax.random.normal(ks[4], (2,)) * 0.5
    D = jax.random.normal(ks[5], (2,))
    got = ssd_op(x, dt, A_log, Bm, Cm, D, chunk=16, interpret=True)
    want = ssd_chunked(x, dt, A_log, Bm, Cm, D, chunk=16)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# cgra_exec: bit-exact vs the cycle-accurate simulator (Morpher validation)
# ---------------------------------------------------------------------------

def _compiled(kernel_name, fabric):
    """Compile via the UAL so identical pairs are mapped once per session
    (the conftest installs a shared mapping cache)."""
    from repro import ual
    program = ual.Program.from_kernel(kernel_name,
                                      n_banks=fabric.n_mem_ports)
    exe = ual.compile(program, ual.Target(fabric))
    assert exe.success, f"{kernel_name} failed to map on {fabric.name}"
    return exe


def _mapped(kernel_name, fabric):
    exe = _compiled(kernel_name, fabric)
    program = exe.program
    return exe.map_result, program.layout, program.make_mem, program.n_iters


@pytest.mark.parametrize("kernel_name", ["gemm", "fft", "adpcm", "aes",
                                         "disparity", "dct", "nw"])
def test_cgra_exec_bitexact_hycube(kernel_name):
    from repro.core.adl import hycube
    from repro.core.dfg import flat_memory
    from repro.kernels.cgra_exec.ops import cgra_exec_op
    from repro.kernels.cgra_exec.ref import cgra_exec_ref
    fab = hycube(4, 4)
    res, layout, mk, n_iters = _mapped(kernel_name, fab)
    rng = np.random.default_rng(5)
    mems = np.stack([flat_memory(layout, mk(rng)) for _ in range(3)])
    got = cgra_exec_op(res.config, mems, n_iters)
    want = cgra_exec_ref(res.config, mems, n_iters)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fabric,kernel_name", [
    pytest.param("n2n", "gemm", id="gemm"),
    pytest.param("n2n", "nw", id="nw"),
    pytest.param("pace", "fft", id="pace-fft"),
    pytest.param("pace", "gemm", id="pace-gemm"),
])
def test_cgra_exec_bitexact_n2n(fabric, kernel_name):
    """N2N 4x4 and PACE 8x8 (64 PEs: the benchmark's fft deployment)."""
    from repro.core.adl import n2n, pace
    from repro.core.dfg import flat_memory
    from repro.kernels.cgra_exec.ops import cgra_exec_op
    from repro.kernels.cgra_exec.ref import cgra_exec_ref
    fab = n2n(4, 4) if fabric == "n2n" else pace()
    res, layout, mk, n_iters = _mapped(kernel_name, fab)
    rng = np.random.default_rng(6)
    mems = np.stack([flat_memory(layout, mk(rng)) for _ in range(2)])
    got = cgra_exec_op(res.config, mems, n_iters)
    want = cgra_exec_ref(res.config, mems, n_iters)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fabric,kernel_name", [
    pytest.param("hycube", k, id=k)
    for k in ("gemm", "fft", "adpcm", "aes", "disparity", "dct", "nw")] + [
    pytest.param("pace", "fft", id="pace-fft")])
def test_cgra_exec_bounded_passes_bitexact(fabric, kernel_name):
    """The compile pipeline's tables bound every LOAD/STORE pass to its
    array's chunks (``LinkedConfig.mem_rows``): bit-exact against the
    cycle-accurate simulator on every kernel, at the default M = 8192."""
    from repro.core.adl import hycube, pace
    from repro.core.lowering import mem_chunk_counts
    from repro.kernels.cgra_exec.ops import cgra_exec_op
    from repro.kernels.cgra_exec.ref import cgra_exec_ref
    fab = hycube(4, 4) if fabric == "hycube" else pace()
    exe = _compiled(kernel_name, fab)
    program = exe.program
    M = program.layout.total_words
    bounded, dense = mem_chunk_counts(exe.lowered, M)
    assert 0 < bounded < dense
    rng = np.random.default_rng(7)
    mems = program.flatten_batch([program.make_mem(rng) for _ in range(3)])
    got = cgra_exec_op(exe.map_result.config, mems, program.n_iters,
                       linked=exe.lowered)
    want = cgra_exec_ref(exe.map_result.config, mems, program.n_iters)
    np.testing.assert_array_equal(got, want)


def test_cgra_exec_matches_dfg_oracle():
    """Three-way agreement: DFG interpreter == simulator == Pallas kernel."""
    from repro.core.adl import hycube
    from repro.core.dfg import flat_memory, interpret, unflatten_memory
    from repro.core.kernel_lib import KERNELS
    from repro.kernels.cgra_exec.ops import cgra_exec_op
    fab = hycube(4, 4)
    dfg, mk, n_iters = KERNELS["gemm"]()
    res, layout, mk, n_iters = _mapped("gemm", fab)
    rng = np.random.default_rng(9)
    mem_named = mk(rng)
    expect = interpret(dfg, mem_named, n_iters)
    flat = flat_memory(layout, mem_named)[None]
    out = cgra_exec_op(res.config, flat, n_iters)[0]
    got = unflatten_memory(layout, out, dfg.arrays)
    for name in dfg.outputs:
        np.testing.assert_array_equal(got[name], expect[name])
