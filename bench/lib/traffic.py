"""The one traffic generator.  A traffic mix is a data file under
``bench/traffic/`` that names its driver and gives its parameters; this
module turns those parameters and ``--seed`` into requests.

Every seed gets the same multiset of sizes and gaps in another order:
sizes and inter-arrival gaps are quantiles of their distributions, laid
out in blocks of ``block`` requests, each block a seeded permutation of the
same quantile grid.  So seeds change the order of the work, not its amount,
and any whole number of blocks holds the same work on every seed."""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def load(name: str, directory: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


def quantile_grid(dist: dict, k: int) -> np.ndarray:
    """``k`` mid-quantiles of a length distribution, as whole numbers
    clipped to ``[min, max]``.  ``dist``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / k) for i in range(k)])
    vals = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


def exp_gap_grid(rate_per_s: float, k: int) -> np.ndarray:
    """``k`` mid-quantiles of the exponential gap of a Poisson process,
    scaled so that their mean is exactly ``1 / rate_per_s``."""
    p = (np.arange(k) + 0.5) / k
    g = -np.log1p(-p)
    return g / g.mean() / rate_per_s


def blocked(rng: np.random.Generator, grid: np.ndarray, n: int) -> np.ndarray:
    """``n`` values: whole blocks, each a permutation of ``grid``."""
    k = len(grid)
    blocks = [grid[rng.permutation(k)] for _ in range(-(-n // k))]
    return np.concatenate(blocks)[:n] if blocks else grid[:0]


def make_rng(seed: int, stream: str) -> np.random.Generator:
    """Independent streams per purpose, so adding one draw never shifts
    another; any non-negative whole ``seed`` works, also past 2**32."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


def requests(mix: dict, seed: int, *, vocab: int, horizon_s: float) -> list[dict]:
    """The mix's requests, sorted by due time.

    Open loop (``rate_per_s`` > 0): due times run from ``-lead_in_s`` and
    stop at ``horizon_s``; gaps come in blocks of ``gap_block`` (default
    ``block``), so a lead-in of whole gap blocks opens the window on a
    block boundary.  Backlog (``rate_per_s`` = 0): ``backlog``
    requests, all due at 0; the first ``in_flight`` of them are part-way
    through their outputs, as the batch of a backlog in its steady state
    is: each has served a share of its output (``progress`` tokens), which
    joins its prompt, and asks for the rest.  The share is fixed per output
    quantile (a fixed permutation of ``k`` mid-quantiles of U(0, 1)), so
    every seed holds the same pairs of output and progress.  With
    ``paired`` the output quantile is a fixed permutation of the prompt's,
    so every whole block holds the same (prompt, output, progress) triples
    on every seed, not only the same lengths: a batch whose work depends on
    which lengths share a request (page reservations, contexts) then does
    the same work on every seed.  Each request is ``{"i", "due_s",
    "prompt", "max_new", "progress"}`` with ``prompt`` an int32 array of
    token ids."""
    k = int(mix.get("block", 64))
    rate = float(mix.get("rate_per_s", 0.0))
    lead = float(mix.get("lead_in_s", 0.0))
    if rate > 0:
        kg = int(mix.get("gap_block", k))
        n_max = int(math.ceil((horizon_s + lead) * rate * 1.5)) + kg
        gaps = blocked(make_rng(seed, "gaps"), exp_gap_grid(rate, kg), n_max)
        # the first request is due at -lead; microsecond rounding keeps
        # block boundaries exact
        due = np.round(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) - lead,
                       6)
        n = int(np.searchsorted(due, horizon_s, side="left"))
        due = due[:n]
    else:
        n = int(mix["backlog"])
        due = np.zeros(n)
    # the lead-in draws its lengths apart, so the window's requests start
    # on a length block boundary too
    n_lead = int(np.sum(due < 0))

    def lengths(stream: str, dist: dict) -> tuple[np.ndarray, np.ndarray]:
        """The lengths and their indices in the quantile grid."""
        idx = np.concatenate([
            blocked(make_rng(seed, stream + ".lead"), np.arange(k), n_lead),
            blocked(make_rng(seed, stream), np.arange(k), n - n_lead)])
        return quantile_grid(dist, k)[idx], idx

    plens, pidx = lengths("prompts", mix["prompt_tokens"])
    if mix.get("paired"):
        oidx = make_rng(0, "pairs").permutation(k)[pidx]
        olens = quantile_grid(mix["output_tokens"], k)[oidx]
    else:
        olens, oidx = lengths("outputs", mix["output_tokens"])
    progress = np.zeros(n, np.int64)
    n_flight = min(n, int(mix.get("in_flight", 0))) if rate == 0 else 0
    if n_flight:
        share = (make_rng(0, "progress").permutation(k) + 0.5) / k
        done = np.floor(share[oidx] * olens).astype(np.int64)
        progress[:n_flight] = np.minimum(done, olens - 1)[:n_flight]
    tok_rng = make_rng(seed, "tokens")
    out = []
    for i in range(n):
        prompt = tok_rng.integers(0, vocab, int(plens[i] + progress[i]),
                                  dtype=np.int32)
        out.append({"i": i, "due_s": float(due[i]), "prompt": prompt,
                    "max_new": int(olens[i] - progress[i]),
                    "progress": int(progress[i])})
    return out
