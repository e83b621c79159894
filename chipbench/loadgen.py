"""The one general traffic generator: a traffic file's parameters and a
seed in, a schedule out. Pure functions of ``(parameters, seed)``.

The arrival processes and the bounded-Pareto lengths are copies of
``serve/loadgen.py`` (``poisson_arrivals``, ``bursty_arrivals``,
``heavy_tailed_lengths``), kept here so that the yardstick cannot move
with the program.
"""

from __future__ import annotations

import math
import random
from typing import NamedTuple, Optional

import numpy as np


def poisson_arrivals(rate: float, horizon_s: float, rng: random.Random) -> list:
    """Arrival offsets in ``[0, horizon_s)`` with exponential gaps."""
    if not rate > 0:
        raise ValueError(f"rate must be > 0, got {rate!r}")
    t, out = 0.0, []
    while True:
        t += rng.expovariate(rate)
        if t >= horizon_s:
            return out
        out.append(t)


def bursty_arrivals(rate_hi: float, rate_lo: float, p_switch: float,
                    horizon_s: float, rng: random.Random) -> list:
    """Two-state Markov-modulated Poisson arrivals (starting hot): the
    state flips with probability ``p_switch`` after every arrival."""
    if not (rate_hi > 0 and rate_lo > 0):
        raise ValueError("rates must be > 0")
    hot, t, out = True, 0.0, []
    while True:
        t += rng.expovariate(rate_hi if hot else rate_lo)
        if t >= horizon_s:
            return out
        out.append(t)
        if rng.random() < p_switch:
            hot = not hot


def draw_length(dist: dict, rng: random.Random) -> int:
    """One length from ``{"dist": "fixed"|"uniform"|"pareto"|"lognormal", ...}``.

    ``pareto`` is bounded Pareto on ``[lo, hi]`` with shape ``alpha``
    (median ``lo * 2**(1/alpha)``); ``lognormal`` has ``median`` and
    ``sigma`` and is clipped to ``[lo, hi]``."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if not 0 < lo <= hi:
        raise ValueError(f"need 0 < lo <= hi, got ({lo}, {hi})")
    if kind == "uniform":
        return rng.randint(lo, hi)
    if kind == "pareto":
        u = max(rng.random(), 1e-12)
        return int(min(hi, max(lo, round(lo / u ** (1.0 / dist["alpha"])))))
    if kind == "lognormal":
        v = math.exp(math.log(dist["median"]) + dist["sigma"] * rng.gauss(0, 1))
        return int(min(hi, max(lo, round(v))))
    raise ValueError(f"unknown length distribution {kind!r}")


class PlannedRequest(NamedTuple):
    due_s: Optional[float]   # open loop: offset from window open
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int
    session: int             # -1: no session


def _tokens(n: int, vocab: int, rng: np.random.Generator) -> np.ndarray:
    # ids from [3, vocab): clear of the usual pad/bos/eos at the bottom
    return rng.integers(3, vocab, size=n, dtype=np.int32)


def make_requests(traffic: dict, seed: int, vocab_size: int,
                  horizon_s: float, lap: int = 0) -> list:
    """The cell's requests from its traffic parameters and the seed.

    Open loop (``loop.kind == "open"``): arrivals over ``horizon_s``
    from ``loop.process`` at the fixed ``loop.rate_per_s``; each arrival
    is the next question of a session. A session (``sessions``) is
    ``questions`` requests over one shared prefix (a document), each
    with its own suffix; sessions are interleaved so that consecutive
    arrivals belong to different sessions where ``interleave`` > 1.
    Closed loop: ``loop.requests`` requests, taken in order by
    ``loop.clients`` clients.

    ``seed`` draws arrivals, lengths and tokens. A traffic file with
    ``schedule_seed`` holds its arrivals and lengths to that ONE
    realisation of their distributions in every run: the amount of work
    is then fixed, and ``seed`` makes the tokens (and the weights). It
    is for a cell whose window holds too few requests for the mix to
    average out, and such a cell reports no tail over requests.

    ``lap`` (a window run as laps, ``loop.laps``) changes the tokens and
    nothing else: every lap has the arrivals and lengths of lap 0.
    """
    rng = random.Random(traffic.get("schedule_seed", seed))
    nrng = np.random.default_rng([seed, lap])
    loop = traffic["loop"]
    if loop["kind"] == "open":
        if loop.get("process", "poisson") == "poisson":
            due = poisson_arrivals(loop["rate_per_s"], horizon_s, rng)
        else:
            due = bursty_arrivals(loop["rate_hi_per_s"], loop["rate_lo_per_s"],
                                  loop["p_switch"], horizon_s, rng)
        n = len(due)
    else:
        n = int(loop["requests"])
        due = [None] * n
    sess = traffic.get("sessions")
    out = []
    if not sess:
        for i in range(n):
            out.append(PlannedRequest(
                due[i],
                _tokens(draw_length(traffic["prompt_len"], rng), vocab_size,
                        nrng),
                draw_length(traffic["output_len"], rng), -1))
        return out
    # sessions: `interleave` sessions are open at once; arrival i goes to
    # the open session (i mod interleave); a session that has asked all
    # its questions is replaced by a new one over a new document
    k = max(1, int(sess.get("interleave", 1)))
    open_sessions: list = []
    next_id = 0
    for i in range(n):
        j = i % k
        if j >= len(open_sessions) or open_sessions[j]["left"] == 0:
            doc = _tokens(draw_length(sess["shared_prefix_len"], rng),
                          vocab_size, nrng)
            s = {"id": next_id, "doc": doc, "left": int(sess["questions"])}
            next_id += 1
            if j >= len(open_sessions):
                open_sessions.append(s)
            else:
                open_sessions[j] = s
        s = open_sessions[j]
        s["left"] -= 1
        suffix = _tokens(draw_length(traffic["prompt_len"], rng), vocab_size,
                         nrng)
        out.append(PlannedRequest(
            due[i], np.concatenate([s["doc"], suffix]),
            draw_length(traffic["output_len"], rng), s["id"]))
    return out
