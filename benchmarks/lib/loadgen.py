"""Seeded traffic for the serving cells: ONE general generator driven by a
traffic file's parameters.

Copied from ``llmtrain_tpu/serving/loadgen.py`` (``build_requests`` /
``run_loadgen``) and changed where that one measures the wrong thing:

* latency counts from when a request was DUE, not from ``submit``: a
  stalled generator or server delays later requests and that wait is the
  user's (``choosing-metrics`` section 5); the original counted from submit;
* the generator's own lateness (submit minus due) is reported, so a starved
  generator is not read as a fast server; the original did not report it;
* lengths are log-normal (median, sigma, clip) per request, not uniform:
  real prompt and output lengths are heavy-tailed;
* a run is a time WINDOW, not a request count: requests are those due in
  the window, and the drain after it is bounded;
* a closed loop exists (each client sends its next request when its last
  is answered) beside the open loop;
* every seed gets the SAME lengths at the SAME due times (drawn from the
  traffic file's ``population_seed``): a seed must not change the amount of
  work nor how it queues. ``--seed`` changes only the token ids;
* probes: after the window, requests for ONE token each over seeded random
  prompts, so that ``correct`` reads the program at positions where the
  next token is a close call (the served tokens of a window repeat one
  token with a wide margin: PERF.md, PR 23).

It imports nothing of the program: requests are plain records; the runner
turns them into the program's request objects.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Planned:
    """One planned request: what to send and (open loop) when it is due."""

    index: int
    prompt_ids: np.ndarray
    max_new_tokens: int
    due_s: float = 0.0  # offset from the window's start; open loop only
    # filled by the runner
    submitted_s: float | None = None
    first_token_s: float | None = None
    token_s: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)
    finished_s: float | None = None
    failed: bool = False  # an error, or no first token by the end of the drain
    truncated: bool = False  # still decoding when the bounded drain ended: not a failure


def lognormal_lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    """Integer lengths: exp(N(ln median, sigma)), clipped to [min, max]."""
    raw = rng.lognormal(math.log(float(spec["median"])), float(spec["sigma"]), n)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(np.int64)


def population(traffic: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The fixed multiset of (prompt length, output length): drawn from the
    traffic file's own ``population_seed``, never from ``--seed``."""
    rng = np.random.default_rng(int(traffic["population_seed"]))
    return (
        lognormal_lengths(rng, n, traffic["prompt_tokens"]),
        lognormal_lengths(rng, n, traffic["output_tokens"]),
    )


def plan_open(traffic: dict, seed: int, seconds: float, vocab_size: int) -> list[Planned]:
    """Poisson arrivals at ``rate_rps`` as a FIXED set of gaps (population
    seed): ``round(rate * (ramp + seconds))`` arrivals whose gaps are unit
    exponentials scaled to fill ``[-ramp_seconds, seconds)``. Requests due
    before 0 load the system and are not measured (``due_s < 0``)."""
    ramp = float(traffic.get("ramp_seconds", 0.0))
    n = max(1, int(round(float(traffic["rate_rps"]) * (ramp + seconds))))
    gaps = np.random.default_rng(int(traffic["population_seed"]) + 1).exponential(1.0, n + 1)
    prompts, outputs = population(traffic, n)
    rng = np.random.default_rng(int(seed))
    offsets = np.cumsum(gaps[:n]) / float(np.sum(gaps)) * (ramp + seconds) - ramp
    return [
        Planned(
            index=i,
            prompt_ids=rng.integers(0, vocab_size, int(prompts[i]), dtype=np.int64).astype(np.int32),
            max_new_tokens=int(outputs[i]),
            due_s=float(offsets[i]),
        )
        for i in range(n)
    ]


def plan_closed(traffic: dict, seed: int, vocab_size: int) -> list[list[Planned]]:
    """Per client, the list of requests it will send one after another.
    ``requests_per_client`` bounds the list; a window ends before it does."""
    clients, per = int(traffic["clients"]), int(traffic["requests_per_client"])
    prompts, outputs = population(traffic, clients * per)
    rng = np.random.default_rng(int(seed))
    out, k = [], 0
    for _ in range(clients):
        mine = []
        for _ in range(per):
            mine.append(Planned(
                index=k,
                prompt_ids=rng.integers(0, vocab_size, int(prompts[k]), dtype=np.int64).astype(np.int32),
                max_new_tokens=int(outputs[k]),
            ))
            k += 1
        out.append(mine)
    return out


def plan_probes(traffic: dict, seed: int, vocab_size: int, n: int) -> list[Planned]:
    """``n`` requests for one token each: prompt lengths of the mix's own
    distribution (fixed by ``population_seed``), token ids from ``seed``."""
    rng = np.random.default_rng(int(traffic["population_seed"]) + 2)
    lengths = lognormal_lengths(rng, n, traffic["prompt_tokens"])
    ids = np.random.default_rng([int(seed), 2])
    return [
        Planned(index=i, prompt_ids=ids.integers(0, vocab_size, int(lengths[i]), dtype=np.int64).astype(np.int32),
                max_new_tokens=1)
        for i in range(n)
    ]


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (the program's convention), None if empty."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def ttft_ms(plans: list[Planned]) -> list[float]:
    """Due -> first token, for requests that got one."""
    return [
        (p.first_token_s - p.due_s) * 1e3 for p in plans if p.first_token_s is not None and not p.failed
    ]


def inter_token_ms(plans: list[Planned], start_s: float = float("-inf"),
                   end_s: float = float("inf")) -> list[float]:
    """Gaps between consecutive tokens of one request, pooled; with bounds,
    only gaps that END inside ``[start_s, end_s]``."""
    out: list[float] = []
    for p in plans:
        out.extend((b - a) * 1e3 for a, b in zip(p.token_s, p.token_s[1:]) if start_s <= b <= end_s)
    return out


def lateness_ms(plans: list[Planned]) -> list[float]:
    return [(p.submitted_s - p.due_s) * 1e3 for p in plans if p.submitted_s is not None]


def p95_with_missing(values: list[float], attempted: int) -> float | None:
    """p95 over ``attempted`` requests where the ones without a value count
    as missing the limit (infinitely late): None once more than 5% miss."""
    if attempted <= 0:
        return None
    rank = max(1, math.ceil(0.95 * attempted))
    ordered = sorted(values)
    return float(ordered[rank - 1]) if rank <= len(ordered) else None
