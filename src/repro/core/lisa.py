"""LISA-lite: a learned placement-bias model for the mapper (paper §III-D).

LISA [HPCA'22] replaces simulated-annealing mapping with GNN-predicted
labels that bias placement.  This is a deliberately small, fully
self-contained analogue: an MLP scores (node, PE) pairs from structural
features; it is trained — with a small AdamW kept here — on (node → chosen
PE) pairs harvested from successful low-II mappings of a training kernel
set, and plugged into the mapper through the ``label_fn`` hook
(`ModuloMapper(label_fn=...)`), biasing the PE ranking of the candidate
enumerator on unseen kernels.

The point is the plumbing the paper calls for (a learned method swapped
into an architecture-adaptive mapper without toolchain changes), not SOTA
mapping quality.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adl import Fabric, MEM_OPS
from repro.core.dfg import DFG
from repro.core.mapper import map_dfg

N_NODE_F = 6
N_PE_F = 5


def node_features(dfg: DFG) -> np.ndarray:
    dfg.compute_asap_alap(4 * len(dfg.nodes))
    horizon = max(1, max(n.alap for n in dfg.nodes))
    rec_nodes = {nid for cyc in dfg.recurrence_cycles() for nid in cyc}
    out = np.zeros((len(dfg.nodes), N_NODE_F), np.float32)
    for n in dfg.nodes:
        out[n.id] = (
            n.asap / horizon,
            n.alap / horizon,
            float(n.op in MEM_OPS),
            len(n.operands) / 3.0,
            len(dfg.users[n.id]) / 4.0,
            float(n.id in rec_nodes),
        )
    return out


def pe_features(fabric: Fabric) -> np.ndarray:
    out = np.zeros((fabric.n_pes, N_PE_F), np.float32)
    for p in range(fabric.n_pes):
        r, c = fabric.pe_xy(p)
        out[p] = (
            r / max(1, fabric.rows - 1),
            c / max(1, fabric.cols - 1),
            float(fabric.pes[p].is_mem),
            c / max(1, fabric.cols - 1),          # distance to mem column 0
            min(r, fabric.rows - 1 - r) / max(1, fabric.rows - 1),
        )
    return out


# ---------------------------------------------------------------------------
# Model: MLP over [node_feat, pe_feat] -> score
# ---------------------------------------------------------------------------

def init_model(key, hidden: int = 32):
    k1, k2 = jax.random.split(key)
    d_in = N_NODE_F + N_PE_F
    return {
        "w1": jax.random.normal(k1, (d_in, hidden)) * (1.0 / d_in ** 0.5),
        "b1": jnp.zeros(hidden),
        "w2": jax.random.normal(k2, (hidden, 1)) * (1.0 / hidden ** 0.5),
        "b2": jnp.zeros(1),
    }


def score(params, nf, pf):
    """nf: (..., N_NODE_F); pf: (..., N_PE_F) -> (...,) logits."""
    x = jnp.concatenate([nf, pf], axis=-1)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return (h @ params["w2"] + params["b2"])[..., 0]


def collect_dataset(kernels: Sequence[Tuple[DFG, int]], fabric: Fabric,
                    seed: int = 0):
    """Harvest (node_feat, chosen_pe) pairs from successful mappings."""
    pf = pe_features(fabric)
    feats, labels = [], []
    for dfg, _ in kernels:
        res = map_dfg(dfg, fabric, seed=seed)
        if not res.success:
            continue
        nf = node_features(dfg)
        for nid, (pe, _t) in res.placements.items():
            feats.append(nf[nid])
            labels.append(pe)
    return np.stack(feats), np.array(labels, np.int32), pf


# AdamW (betas 0.9/0.95, eps 1e-8, no weight decay) with global-norm
# clipping at 1.0; the step size warms up linearly over 10 steps, then
# decays on a cosine to a tenth of ``lr`` at the last step.
_BETAS, _EPS, _CLIP, _WARMUP = (0.9, 0.95), 1e-8, 1.0, 10


def _lr_at(lr: float, step, total_steps: int):
    warm = jnp.minimum(step / _WARMUP, 1.0)
    prog = jnp.clip((step - _WARMUP) / max(1, total_steps - _WARMUP),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return lr * warm * (0.1 + 0.9 * cos)


def _adamw_step(params, grads, m, v, step, lr):
    """One update at (1-based) ``step``; returns (params, m, v)."""
    b1, b2 = _BETAS
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, _CLIP / (gnorm + 1e-9))

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        denom = jnp.sqrt(v / (1 - b2 ** step)) + _EPS
        return p - lr * (m / (1 - b1 ** step) / denom), m, v

    flat, tdef = jax.tree.flatten(params)
    out = [upd(*a) for a in zip(flat, *(tdef.flatten_up_to(t)
                                        for t in (grads, m, v)))]
    return tuple(tdef.unflatten([o[i] for o in out]) for i in range(3))


def train(feats: np.ndarray, labels: np.ndarray, pf: np.ndarray,
          steps: int = 300, lr: float = 1e-2, seed: int = 0):
    """Softmax-over-PEs classification, trained with AdamW."""
    params = init_model(jax.random.PRNGKey(seed))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    X = jnp.asarray(feats)                        # (N, F)
    y = jnp.asarray(labels)                       # (N,)
    P = jnp.asarray(pf)                           # (n_pes, PF)

    def loss_fn(prm):
        logits = score(prm, X[:, None, :].repeat(P.shape[0], 1),
                       P[None, :, :].repeat(X.shape[0], 0))   # (N, n_pes)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], 1).mean()

    loss_grad = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for step in range(1, steps + 1):
        loss, grads = loss_grad(params)
        t = jnp.asarray(step, jnp.int32)
        params, m, v = _adamw_step(params, grads, m, v, t,
                                   _lr_at(lr, t, steps))
        losses.append(float(loss))
    return params, losses


def make_label_fn(params, fabric: Fabric, weight: float = 0.5,
                  mem_only: bool = True) -> Callable[[DFG], Callable]:
    """Returns dfg -> label_fn(nid, pe, II) for ``map_dfg(label_fn=...)``.

    The bias is normalized to [0, weight) per node so it acts as a
    TIEBREAK on the mapper's proximity ranking (LISA labels guide, the
    router still decides) rather than overriding feasibility-driven
    placement.

    ``mem_only`` (measured ablation, examples/learned_mapper.py): the
    absolute-PE labels this small model learns transfer well for MEMORY
    nodes (mem-capable column structure is fabric-invariant) but mislead
    for compute nodes on unseen kernels (II 4->8 on nw even at weight
    0.2) — real LISA uses *relative* GNN labels for exactly this reason.
    Default applies the learned bias to memory nodes only, which gives
    II parity with no restart inflation on the held-out set.
    """
    pf = jnp.asarray(pe_features(fabric))

    def for_dfg(dfg: DFG):
        nf = jnp.asarray(node_features(dfg))
        logits = score(params, nf[:, None, :].repeat(pf.shape[0], 1),
                       pf[None, :, :].repeat(nf.shape[0], 0))
        p = np.asarray(jax.nn.softmax(logits, -1))
        bias = weight * (1.0 - p / p.max(axis=1, keepdims=True))
        if mem_only:
            bias = bias * np.asarray(nf[:, 2:3])   # is_mem feature

        def label_fn(nid: int, pe: int, II: int) -> float:
            return float(bias[nid, pe])
        return label_fn
    return for_dfg
