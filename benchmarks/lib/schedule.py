"""The one traffic generator: a traffic file in, a fixed schedule out.

The schedule — every request's due time, prompt length and output length —
comes from the traffic file alone (its distributions and its own
``schedule_seed``), never from ``--seed``: every run of a cell, on every
commit, offers the same requests at the same offsets.  ``--seed`` makes the
weights and the prompt token ids.  Requests are drawn one after another
(gap, prompt, output), so a longer window extends the stream and changes
nothing before it.
"""

import hashlib
import struct
from typing import List, NamedTuple

import numpy as np


class Scheduled(NamedTuple):
    due_s: float        # seconds from the opening of the window; < 0 = ramp
    prompt_len: int
    output_len: int


def _draw_len(rng, spec):
    """One length from ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "uniform", "min", "max"}`` or ``{"dist":
    "fixed", "value"}``."""
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "uniform":
        return int(rng.integers(spec["min"], spec["max"] + 1))
    if kind == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"]))
        return int(min(max(round(float(x)), spec["min"]), spec["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def build_schedule(traffic: dict, window_s: float) -> List[Scheduled]:
    """Requests due from ``-ramp_s`` to the end of the window.

    ``arrival: open-loop`` — Poisson arrivals at ``rate_rps`` (exponential
    gaps), or bursts of ``burst`` requests at one instant with the same
    mean rate.  ``arrival: backlog`` — requests until their tokens reach
    ``backlog_tokens``, all due before the ramp starts.
    """
    rng = np.random.Generator(np.random.PCG64(int(traffic["schedule_seed"])))
    ramp = float(traffic.get("ramp_s", 0.0))
    out: List[Scheduled] = []
    arrival = traffic["arrival"]
    if arrival == "backlog":
        tokens = 0
        while tokens < int(traffic["backlog_tokens"]):
            p = _draw_len(rng, traffic["prompt_len"])
            o = _draw_len(rng, traffic["output_len"])
            out.append(Scheduled(-ramp, p, o))
            tokens += p + o
        return out
    if arrival != "open-loop":
        raise ValueError(f"unknown arrival {arrival!r}")
    rate = float(traffic["rate_rps"])
    burst = int(traffic.get("burst", 1))
    t = -ramp
    while True:
        t += float(rng.exponential(burst / rate))
        if t >= window_s:
            return out
        for _ in range(burst):
            out.append(Scheduled(t, _draw_len(rng, traffic["prompt_len"]),
                                 _draw_len(rng, traffic["output_len"])))


def digest(schedule) -> str:
    """blake2b over the due times and lengths: two runs that print the
    same digest offered the same load."""
    h = hashlib.blake2b(digest_size=8)
    for s in schedule:
        h.update(struct.pack("<dii", s.due_s, s.prompt_len, s.output_len))
    return h.hexdigest()


def prompt_tokens(schedule, seed: int, vocab_size: int):
    """Prompt token ids from ``--seed``: one draw for the whole schedule."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    flat = rng.integers(1, vocab_size, sum(s.prompt_len for s in schedule),
                        dtype=np.int64)
    out, at = [], 0
    for s in schedule:
        out.append(flat[at:at + s.prompt_len].tolist())
        at += s.prompt_len
    return out
