"""The one general traffic generator: a mix's parameters → a request schedule.

A mix file (``<mix>.json`` beside this module) names this module as its
``generator`` and gives:

  loop        "closed": ``clients`` callers, each sending its next request
              when its last one returns; "open": requests due on a
              schedule whatever the server does
  arrivals    open loop only: "poisson" at ``rate_qps``
  query_pool  distinct queries made with the corpus; each request draws
              one uniformly
  predicate   the predicate file under ``bench/predicates/`` that turns a
              query's attributes into its filter (read by the harness)

A Poisson schedule is drawn conditioned on its count: exactly
round(rate × seconds) arrivals, uniform over the window and sorted. Every
seed then offers the same amount of work in another order, so seeds differ
by arrangement, not by load.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

#: closed-loop draws made up front (a window never completes more)
CLOSED_DRAWS = 1 << 18


@dataclasses.dataclass
class Schedule:
    loop: str  # "closed" | "open"
    clients: int  # closed loop: concurrent callers
    due: np.ndarray  # open loop: offsets (s) into the window, sorted
    queries: np.ndarray  # pool index of the i-th request sent


def make_schedule(mix: dict, rng: np.random.Generator, seconds: float,
                  rate_qps: Optional[float] = None) -> Schedule:
    """``rate_qps`` overrides an open mix's rate (the knee sweep)."""
    pool = int(mix["query_pool"])
    if mix["loop"] == "closed":
        return Schedule("closed", int(mix["clients"]), np.zeros(0),
                        rng.integers(0, pool, CLOSED_DRAWS))
    if mix["loop"] != "open" or mix.get("arrivals") != "poisson":
        raise ValueError(f"unsupported mix {mix!r}")
    rate = float(rate_qps if rate_qps is not None else mix["rate_qps"])
    if not rate > 0:
        raise ValueError(f"an open mix needs a positive rate, not {rate}")
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return Schedule("open", 0, due, rng.integers(0, pool, n))
