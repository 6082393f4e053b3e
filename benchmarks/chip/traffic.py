"""The one traffic generator: a traffic file's parameters -> a schedule.

A traffic file (traffic/<name>.json) describes sessions:

- ``shared_prefix``: tokens of one system prompt that opens every
  session's first prompt (0 = none);
- ``turns``: [lo, hi], turns per session, uniform;
- ``first_turn`` / ``later_turn`` / ``output``: lognormal lengths given
  by their median and sigma, clipped to [min, max] (user turns and
  answer budgets);
- ``think_s``: [lo, hi], seconds between one turn's arrival and the
  next turn of the same session, uniform;
- ``max_context``: a session ends at the first turn whose prompt plus
  budget would exceed it (that turn is not sent);
- ``sessions_per_s``: Poisson rate of session starts;
- ``lead_in_s``: sessions also start in [-lead_in_s, 0), so the window
  opens on the steady mix of turn numbers; only turns that arrive in
  [0, seconds) are sent;
- ``template_seed``: fixes the set of sessions (turn counts, lengths,
  think times) and of start times.

The run's seed draws every token, and nothing else: every seed carries
the same requests, of the same sizes, arriving at the same times in the
same order, so runs on different seeds do the same work.  Turn k+1's
prompt is turn k's prompt, then turn k's answer (synthetic tokens, as
many as its budget: the engine takes its whole schedule before it
runs), then the new user turn.  Single-request traffic is the case
turns = [1, 1] with no shared prefix.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Tuple

import numpy as np


@dataclass
class Turn:
    rid: int
    session: int
    turn: int
    t_arrival: float
    prompt: np.ndarray  # int32 token ids
    budget: int


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed (negative or past 64 bits)."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)) % 2 ** 64, int(seed < 0), *salt]))


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _templates(t: dict, seconds: float):
    """(start times, per-session [(user_len, budget, think)...]) from
    template_seed alone."""
    rng = np.random.default_rng(int(t["template_seed"]))
    lead, rate = float(t["lead_in_s"]), float(t["sessions_per_s"])
    span = lead + float(seconds)
    n = int(rng.poisson(rate * span)) if rate > 0 else 0
    starts = np.sort(rng.uniform(-lead, float(seconds), n))
    lo, hi = t["turns"]
    sessions = []
    for _ in range(n):
        k = int(rng.integers(lo, hi + 1))
        users = np.concatenate([_lengths(rng, t["first_turn"], 1),
                                _lengths(rng, t["later_turn"], k - 1)])
        buds = _lengths(rng, t["output"], k)
        think = rng.uniform(t["think_s"][0], t["think_s"][1], k)
        sessions.append(list(zip(users.tolist(), buds.tolist(),
                                 think.tolist())))
    return starts, sessions


def generate(t: dict, vocab: int, seed: int, seconds: float) -> List[Turn]:
    """The schedule for one run: turns arriving in [0, seconds), sorted by
    arrival, rid in that order."""
    starts, sessions = _templates(t, seconds)
    rng = seed_rng(seed, 1)
    prefix = rng.integers(0, vocab, int(t["shared_prefix"])).astype(np.int32)
    limit = int(t["max_context"])
    out = []
    for si, start in enumerate(starts):
        history, t_k = prefix, float(start)
        for k, (user, bud, think) in enumerate(sessions[si]):
            if k:
                t_k += think
            prompt = np.concatenate(
                [history, rng.integers(0, vocab, user).astype(np.int32)])
            if len(prompt) + bud > limit:
                break
            if 0.0 <= t_k < seconds:
                out.append((t_k, si, k, prompt, bud))
            answer = rng.integers(0, vocab, bud).astype(np.int32)
            history = np.concatenate([prompt, answer])
    out.sort(key=lambda x: x[0])
    return [Turn(rid=i, session=s, turn=k, t_arrival=ta, prompt=p,
                 budget=int(b)) for i, (ta, s, k, p, b) in enumerate(out)]


def bounds(t: dict) -> Tuple[int, int]:
    """(longest prompt, longest output) any schedule of this file holds."""
    longest_out = int(t["output"]["max"])
    first = int(t["shared_prefix"]) + int(t["first_turn"]["max"])
    most = int(t["max_context"]) - int(t["output"]["min"])
    one_turn = t["turns"][1] <= 1
    return (min(first, most) if one_turn else most), longest_out


def prompt_range(t: dict) -> Tuple[int, int]:
    """(shortest, longest) prompt any schedule of this file holds."""
    return (int(t["shared_prefix"]) + int(t["first_turn"]["min"]),
            bounds(t)[0])

