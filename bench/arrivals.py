"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix file holds parameters only:

    kind            "open": Poisson arrivals at ``rate_per_s``
    prompt_tokens   {"dist": "lognormal", "median", "sigma", "min", "max"}
    output_tokens   or {"dist": "uniform", "min", "max"}
    private_share   share of requests whose prompts carry personal
                    phrases
    warmup_s        seconds of the same traffic served before the window
    schedule_seed   the seed of the schedule: arrival times, lengths and
                    which requests are private

Every draw is stratified: a segment of n requests takes its lengths (and
its inter-arrival gaps) at the n quantiles (i + 0.5) / n of the
distribution, in an order drawn from ``schedule_seed``.  The run's own
seed draws the prompts' text (and the weights), so every run of a mix
offers the same work at the same times: tails over a few dozen requests
would otherwise swing with the order in which a seed happens to put the
long requests.

Prompts are ASCII text of the drawn token length (the serving tokenizer
is byte level: BOS, one token per byte, and the trailing space the
engine appends).  Non-private text is built from ``NEUTRAL_WORDS`` (no
digits, no personal pronouns, no detector keyword); a private prompt
starts with one of ``PRIVATE_PHRASES``, each of which the privacy
detector's rules catch.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

NEUTRAL_WORDS = (
    "river stone table window garden yellow bright quiet morning paper "
    "orange forest bridge cloud summer winter autumn spring coffee music "
    "piano violin painting castle harbor island mountain valley meadow "
    "silver golden purple green blue red white black circle square "
    "triangle planet comet galaxy orbit rocket engine wheel tunnel "
    "ladder basket pencil marble copper timber canvas lantern feather "
    "pebble shadow sunset sunrise thunder breeze puzzle riddle story "
    "chapter verse poem novel author museum statue fountain plaza market "
    "bakery bread butter cheese apple pear grape lemon cherry melon "
    "tomato carrot potato pepper garlic onion salad soup noodle rice "
    "tea kettle teapot saucer plate fork spoon napkin candle mirror "
    "carpet curtain pillow blanket sofa chair desk shelf drawer closet "
    "kitchen hallway attic porch fence gate path trail road map compass "
    "sail boat anchor wave tide shore sand shell coral reef whale "
    "dolphin otter beaver rabbit squirrel sparrow robin eagle falcon owl "
    "tiger zebra giraffe camel llama koala panda turtle lizard frog "
    "explain describe compare summarize list translate sort count draw "
    "simple gentle rapid slow tall short wide narrow heavy light "
    "ancient modern rustic elegant sturdy fragile smooth rough warm cool"
).split()

PRIVATE_PHRASES = (
    "my doctor said", "my salary is", "my wife asked", "my ssn is",
    "my password is", "my home is", "my kids need", "my debt grew",
    "my husband saw", "my mother has", "my birthday is", "my account",
)

BOS = 1
BYTE_OFFSET = 3


def load(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") != "open":
        raise ValueError(f"{path}: kind must be 'open'")
    return mix


def token_ids(prompt: str) -> List[int]:
    """The ids the serving tokenizer gives ``prompt`` as the engine
    submits it (BOS, bytes + 3, the appended space)."""
    return [BOS] + [b + BYTE_OFFSET for b in (prompt + " ").encode()]


def quantile(dist: dict, u: float) -> int:
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"]
                                      * NormalDist().inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """n points (i + 0.5) / n in a seed-drawn order."""
    return (rng.permutation(n) + 0.5) / n


def prompt_text(n_tokens: int, private: bool,
                rng: np.random.Generator) -> str:
    """ASCII text that tokenizes to exactly ``n_tokens`` ids."""
    n_chars = n_tokens - 2
    parts = [PRIVATE_PHRASES[rng.integers(len(PRIVATE_PHRASES))]] \
        if private else []
    size = sum(len(p) + 1 for p in parts)
    while size < n_chars:
        w = NEUTRAL_WORDS[rng.integers(len(NEUTRAL_WORDS))]
        parts.append(w)
        size += len(w) + 1
    text = " ".join(parts)[:n_chars]
    return text.rstrip() + "x" * (n_chars - len(text.rstrip()))


@dataclass
class Request:
    rid: int
    due: float              # seconds after the traffic's time zero
    prompt: str
    prompt_tokens: int
    max_new: int
    private: bool


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def open_segment(mix: dict, seed: int, stream: int, start: float,
                 seconds: float, rid0: int) -> List[Request]:
    """Open-loop arrivals due in [start, start + seconds): exactly
    round(rate * seconds) requests, gaps at stratified exponential
    quantiles scaled to fill the segment."""
    n = int(round(mix["rate_per_s"] * seconds))
    if n <= 0:
        return []
    plan = _rng(mix["schedule_seed"], stream)
    gaps = -np.log1p(-stratified(n, plan))
    gaps *= seconds / gaps.sum()
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    p_u, o_u = stratified(n, plan), stratified(n, plan)
    n_priv = int(round(mix.get("private_share", 0.0) * n))
    private = plan.permutation(np.arange(n) < n_priv)
    rng = _rng(seed, stream)
    out = []
    for i in range(n):
        L = quantile(mix["prompt_tokens"], p_u[i])
        out.append(Request(rid0 + i, float(due[i]),
                           prompt_text(L, bool(private[i]), rng), L,
                           quantile(mix["output_tokens"], o_u[i]),
                           bool(private[i])))
    return out


def load_named(root: Path, name: str) -> dict:
    return load(root / "traffic" / f"{name}.json")
