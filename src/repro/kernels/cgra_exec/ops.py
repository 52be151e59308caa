"""Public wrapper: MachineConfig -> lowered tables -> Pallas execution."""
from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np

from repro.core.lowering import (LinkedConfig, config_fingerprint,
                                 link_config)
from repro.core.machine import MachineConfig

#: fingerprint-keyed memo for callers that pass ``linked=None``: external
#: one-shot users (tests, scripts) used to silently re-lower the same
#: config on every call — now every distinct configuration is lowered at
#: most once per process, mirroring the UAL pipeline's lowered-artifact
#: cache for callers that bypass the pipeline
_LINKED_MEMO: Dict[str, LinkedConfig] = {}
_LINKED_LOCK = threading.Lock()


def _memoized_link(cfg: MachineConfig) -> LinkedConfig:
    fp = config_fingerprint(cfg)
    with _LINKED_LOCK:
        linked = _LINKED_MEMO.get(fp)
    if linked is None:
        linked = link_config(cfg)
        with _LINKED_LOCK:
            linked = _LINKED_MEMO.setdefault(fp, linked)
    return linked


def cgra_exec_op(cfg: MachineConfig, mem: np.ndarray, n_iters: int, *,
                 lanes: int = 128,
                 linked: Optional[LinkedConfig] = None) -> np.ndarray:
    """Execute a mapped CGRA configuration over a batch of test vectors.

    mem: (B, M) int32 scratchpad images.  ``linked``
    supplies a precomputed lowered artifact (e.g. the one memoized by the
    ``ual`` compile pipeline); when omitted the config is lowered through
    a per-process fingerprint memo, so no caller lowers the same
    configuration twice.  Execution goes through the persistent JIT
    engine (``repro.ual.engine``): repeat calls on one configuration hit
    warm traces instead of rebuilding the ``pallas_call``.
    """
    if linked is None:
        linked = _memoized_link(cfg)
    from repro.ual.engine import default_engine
    out, _ = default_engine().run(linked, np.asarray(mem, np.int32), n_iters,
                                  lanes=lanes)
    return out
