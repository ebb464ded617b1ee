"""The one traffic generator.  A mix is a data file of parameters; this
reads it and lays out, from ``--seed``, what the window will send.

  loop "closed"  backlogs of ``backlog_pods`` pending pods, each sent as
                 one coalesced hint frame, the next the moment the last
                 pod of this one is answered (one client, as the host
                 scheduler is)
  loop "open"    arrivals on a schedule fixed before the window: a
                 Poisson process at ``rate_pods_per_s``, or piecewise at
                 the rates of ``segments`` (share of the window, pods/s)
                 where a mix gives them

Every seed gets the same work.  An open schedule's gaps are the
exponential distribution's own quantiles at the segment's rate, one per
expected arrival, and the seed only permutes them: the same set of gaps,
the same count and the same length for every seed, in another order.
(The program's ``loadgen/arrivals.py`` draws the gaps at random, so two
seeds there differ in count and length.)
"""

from __future__ import annotations

import json
import math
import random


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def resolve(value, config: dict) -> int:
    """A size in a mix is a number, or the name of a key of the
    configuration (``"measure_pods"``), so one mix serves configurations
    whose backlogs differ."""
    if isinstance(value, str):
        return int(config[value])
    return int(value)


def segment_offsets(rate_per_s: float, seconds: float, rng: random.Random) -> list[float]:
    """Arrival offsets in ``[0, seconds)`` at ``rate_per_s``: n = rate ×
    seconds gaps, the k-th the exponential's (k + ½)/n quantile, permuted
    by ``rng`` and scaled so that they fill the segment exactly."""
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (k + 0.5) / n) / rate_per_s for k in range(n)]
    rng.shuffle(gaps)
    scale = seconds / sum(gaps)
    out = []
    t = 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    # the last offset is the segment's end; keep it strictly inside
    out[-1] = min(out[-1], seconds * (1.0 - 1e-9))
    return out


def open_offsets(mix: dict, seconds: float, seed: int, rate_override: float | None = None) -> list[float]:
    rng = random.Random((seed << 2) ^ 0xA221)
    segments = mix.get("segments") or [{"share": 1.0, "rate_pods_per_s": mix["rate_pods_per_s"]}]
    out: list[float] = []
    t0 = 0.0
    for seg in segments:
        length = seconds * float(seg["share"])
        rate = float(seg["rate_pods_per_s"])
        if rate_override is not None:
            # a study's sweep scales every segment alike
            rate = rate * rate_override / float(mix["rate_pods_per_s"])
        out.extend(t0 + off for off in segment_offsets(rate, length, rng))
        t0 += length
    return out
