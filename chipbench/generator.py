"""The one traffic generator: inputs and arrival times from ``--seed``
and the parameters of a mix (``traffic/<mix>.json``).

Every seed gets the same amount of work: a pool of the same size and,
for an open loop, the same number of arrivals, placed as a Poisson
process conditioned on that count (sorted uniform times), so that seeds
change the order and the gaps and not how much is offered.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

# independent random streams drawn from one seed
STREAM_INPUTS, STREAM_ARRIVALS, STREAM_SAMPLE = 1, 2, 3


#: the parameters each kind of loop reads; a mix that sets any other is
#: refused, so that no parameter is silently ignored
PARAMS = {"closed": {"chunk", "pool", "buckets", "sample_every"},
          "open": {"rate_per_s", "pool", "buckets"}}


def load_mix(name: str) -> Dict[str, object]:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    want = PARAMS.get(mix.get("loop"))
    if want is None:
        raise ValueError(f"mix {name!r}: loop must be one of {sorted(PARAMS)}")
    got = set(mix) - {"loop", "about"}
    if got != want:
        raise ValueError(f"mix {name!r}: parameters {sorted(got)}, "
                         f"the {mix['loop']} loop reads {sorted(want)}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``STREAM_*``) of one seed; any whole
    number is a valid seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _twiddle(spec: Dict[str, object], n: int, length: int) -> np.ndarray:
    k = np.arange(length)
    angle = 2 * np.pi * k / int(spec["points"])
    if spec["dist"] == "twiddle_cos_q8":
        row = np.round(256 * np.cos(angle))
    else:
        row = np.round(-256 * np.sin(angle))
    return np.broadcast_to(row.astype(np.int32), (n, length)).copy()


def make_inputs(config: Dict[str, object], seed: int, n: int
                ) -> Dict[str, np.ndarray]:
    """``n`` input images as named (n, length) int32 arrays, from the
    configuration's ``inputs`` spec."""
    rng = rng_for(seed, STREAM_INPUTS)
    out = {}
    for name, spec in config["inputs"].items():
        length = int(spec["length"])
        if spec["dist"] == "uniform":
            out[name] = rng.integers(int(spec["low"]), int(spec["high"]),
                                     size=(n, length), dtype=np.int32)
        elif spec["dist"] in ("twiddle_cos_q8", "twiddle_sin_q8"):
            out[name] = _twiddle(spec, n, length)
        else:
            raise ValueError(f"input {name!r}: unknown dist {spec['dist']!r}")
    return out


def as_requests(batch: Dict[str, np.ndarray]) -> List[Dict[str, np.ndarray]]:
    """Named (n, length) arrays -> n per-image dicts of row views."""
    n = len(next(iter(batch.values())))
    return [{name: arr[i] for name, arr in batch.items()} for i in range(n)]


def arrivals(mix: Dict[str, object], seed: int, seconds: float
             ) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop:
    ``rate_per_s * seconds`` arrivals, Poisson given their count."""
    n = int(math.floor(float(mix["rate_per_s"]) * seconds))
    rng = rng_for(seed, STREAM_ARRIVALS)
    return np.sort(rng.uniform(0.0, seconds, size=n))
