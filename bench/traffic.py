"""Traffic generation for the benchmark: one general generator that turns a
traffic mix (a JSON file of parameters under ``bench/traffic/``) into a
request schedule.

The length and arrival arithmetic is copied from the program's
``serve/loadgen.py`` (lognormal lengths clipped into a range, gamma
inter-arrival gaps with squared coefficient of variation ``cv2``, tenants
with a shared system prefix), so that a later change to the program cannot
move the yardstick.

Every seed gets the same work.  The *sizes* -- each request's tenant,
prompt length and output budget, and the multiset of arrival gaps -- are
drawn from a stream fixed by the mix alone; ``--seed`` draws the token ids
and tenant prefixes.  An open-loop phase is consumed whole, so there the
seed also permutes the sizes and gaps: two seeds offer the same requests,
tokens and burst sizes, in another order.  A closed loop consumes as many
requests as the server completes, so its sequence of sizes is the same for
every seed, in the same order: whatever prefix of it a window reaches is
the same work.  Either way the spread between runs is the system's and not
the generator's.

Two loops:

* ``open``: arrivals on a schedule, whatever the server does.  The window's
  ``rate_rps * seconds`` arrivals are spread over the window with gaps whose
  shape is gamma and whose sum is exactly the window, so the offered rate
  is the cell's rate in every run.
* ``closed``: ``clients`` callers, each sending its next request when the
  last one completes.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["Req", "sample_length", "tenant_prefixes", "open_schedule",
           "closed_requests"]


@dataclass
class Req:
    """One request of a schedule: due offset (seconds from the start of
    its phase; 0 for closed-loop requests), tenant index (-1: none), the
    length of its shared prefix, its prompt and output budget."""

    t_s: float
    tenant: int
    shared: int
    prompt: List[int]
    max_new: int


def sample_length(dist: Dict, rng: random.Random) -> int:
    """One integer draw from a length spec: ``fixed`` (``value``),
    ``uniform`` (``lo``..``hi`` inclusive) or ``lognormal`` (``median``,
    ``sigma``, clipped into ``[lo, hi]``)."""
    kind = dist["kind"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        return rng.randint(int(dist["lo"]), int(dist["hi"]))
    if kind == "lognormal":
        v = rng.lognormvariate(math.log(float(dist["median"])),
                               float(dist["sigma"]))
        return int(min(max(round(v), dist["lo"]), dist["hi"]))
    raise ValueError(f"unknown length distribution kind {kind!r}")


def _zipf_weights(n: int, alpha: float) -> List[float]:
    return [1.0 / (k ** alpha) for k in range(1, n + 1)]


def _sizes(mix: Dict, n: int, stream: str) -> List[Tuple[int, int, int]]:
    """``n`` (tenant, prompt_len, output_len) triples from the mix's fixed
    stream ``stream`` -- the same for every seed."""
    rng = random.Random(f"{mix['name']}:{stream}:sizes")
    ten = mix.get("tenants")
    out = []
    for _ in range(n):
        t = -1
        if ten:
            t = rng.choices(range(ten["count"]),
                            weights=_zipf_weights(ten["count"],
                                                  ten["zipf"]))[0]
        out.append((t, sample_length(mix["prompt_len"], rng),
                    max(1, sample_length(mix["output_len"], rng))))
    return out


def _gaps(mix: Dict, n: int, span_s: float, stream: str) -> List[float]:
    """``n`` gamma-shaped gaps (shape ``1/cv2``) from the fixed stream,
    scaled to sum to ``span_s``: the offered rate is exactly ``n/span_s``."""
    rng = random.Random(f"{mix['name']}:{stream}:gaps")
    cv2 = float(mix["arrivals"]["cv2"])
    g = [rng.gammavariate(1.0 / cv2, cv2) for _ in range(n)]
    s = sum(g)
    return [x * span_s / s for x in g]


def tenant_prefixes(mix: Dict, vocab: int, seed: int) -> List[List[int]]:
    """Each tenant's shared prefix: token ids drawn from the seed."""
    ten = mix.get("tenants")
    if not ten:
        return []
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(1, vocab, ten["prefix_tokens"]).tolist()
            for _ in range(ten["count"])]


def _materialize(sizes, prefixes, vocab: int, rng: np.random.Generator,
                 times) -> List[Req]:
    reqs = []
    for t_s, (tenant, plen, out) in zip(times, sizes):
        pre = prefixes[tenant] if tenant >= 0 else []
        user = rng.integers(1, vocab, plen).tolist()
        reqs.append(Req(t_s, tenant, len(pre), list(pre) + user, out))
    return reqs


def open_schedule(mix: Dict, rate_rps: float, span_s: float, seed: int,
                  vocab: int, phase: str,
                  prefixes: Optional[List[List[int]]] = None) -> List[Req]:
    """The ``round(rate_rps * span_s)`` arrivals of one phase (``"warmup"``
    or ``"window"``), due offsets in ``[0, span_s)``.  Sizes and gaps come
    from the fixed stream of ``phase``; the seed permutes both and draws
    the tokens."""
    n = max(1, round(rate_rps * span_s))
    sizes = _sizes(mix, n, phase)
    gaps = _gaps(mix, n, span_s, phase)
    perm = random.Random(f"{seed}:{phase}:order")
    perm.shuffle(sizes)
    perm.shuffle(gaps)
    # the first arrival is due at 0, the next after the first gap, ...
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]).tolist()
    if prefixes is None:
        prefixes = tenant_prefixes(mix, vocab, seed)
    rng = np.random.default_rng([seed, 2 if phase == "window" else 3])
    return _materialize(sizes, prefixes, vocab, rng, times)


def closed_requests(mix: Dict, seed: int, vocab: int) -> List[Req]:
    """The mix's ``requests`` closed-loop requests, in the order clients
    take them: the fixed stream's sizes in its own order (the same for every
    seed), with token ids drawn from the seed."""
    n = int(mix["requests"])
    sizes = _sizes(mix, n, "closed")
    rng = np.random.default_rng([seed, 2])
    return _materialize(sizes, tenant_prefixes(mix, vocab, seed), vocab, rng,
                        [0.0] * n)
