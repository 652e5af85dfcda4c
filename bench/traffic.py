"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and a seed into a plan of sessions.

Sessions arrive open-loop at ``session_rate_per_s``. Sizes, turn counts,
think times and arrival gaps are stratified quantiles of the mix's
distributions, put in order by the mix's ``schedule_seed``: every run
sends the same sessions at the same times. The run's seed draws the prompt
text and which node is each session's home (the nodes are alike). A window
sees a few dozen sessions, and an order drawn from the run's seed changed
how much work landed in it by a factor of three (PERF.md). Prompt text is
words of
capital letters and spaces, which the program's byte-level tokenizer keeps
at one token per byte (its merges are learned from lower-case words only),
so a prompt of ``n`` characters is ``n`` tokens.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
_LETTERS = np.array([ord(c) for c in "ABCDEFGHIJKLMNOPQRSTUVWXYZ"], np.uint8)


@dataclass(frozen=True)
class Turn:
    prompt: str
    prompt_tokens: int
    max_new: int
    node: str
    think_ms: float      # delay after the previous turn's response (0 first)
    roam: bool           # first turn of the session on a node it was not on


@dataclass(frozen=True)
class Session:
    idx: int
    start_ms: float
    turns: Tuple[Turn, ...]


@dataclass(frozen=True)
class Plan:
    sessions: Tuple[Session, ...]


def load_mix(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def quantile_values(spec: Dict, n: int) -> List[float]:
    """``n`` stratified quantiles ``F^-1((i + 0.5) / n)`` of a length or time
    distribution, clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "exponential":
        v = -spec["mean"] * np.log1p(-u)
    elif dist == "uniform_int":
        v = spec["min"] + np.floor(u * (spec["max"] - spec["min"] + 1))
    elif dist == "bounded_pareto":
        lo, hi, a = spec["min"], spec["max"] + 1, spec["alpha"]
        v = np.floor(lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(v, spec["min"], spec.get("max", np.inf)).tolist()


def _ints(spec: Dict, n: int, rng: np.random.Generator) -> List[int]:
    return [int(round(x)) for x in rng.permutation(quantile_values(spec, n))]


def prompt_text(rng: np.random.Generator, n: int) -> str:
    """``n`` characters of capital-letter words, each led by a space."""
    out = bytearray()
    while len(out) < n:
        out.append(32)
        out.extend(rng.choice(_LETTERS, int(rng.integers(2, 9))).tobytes())
    return out[:n].decode("ascii")


def generate(mix: Dict, seed: int, nodes: Sequence[str]) -> Plan:
    """The plan of sessions for one run. Pure: the same (mix, seed, nodes)
    gives the same plan."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(int(mix["schedule_seed"]))
    n_sess = int(mix["sessions"])
    n_turns = _ints(mix["turns"], n_sess, order)
    total = sum(n_turns)
    prompts = _ints(mix["prompt_tokens"], total, order)
    outputs = _ints(mix["output_tokens"], total, order)
    thinks = [float(x) * 1e3 for x in order.permutation(
        quantile_values(mix["think_s"], max(1, total - n_sess)))]
    gaps = order.permutation(quantile_values(
        {"dist": "exponential", "mean": 1.0 / mix["session_rate_per_s"],
         "min": 0.0}, n_sess))
    starts = np.cumsum(gaps) * 1e3
    shift = int(rng.integers(len(nodes)))
    homes = [nodes[(i + shift) % len(nodes)] for i in order.permutation(n_sess)]
    n_roam = int(round(mix.get("roam_share", 0.0) * n_sess))
    first_roam = max(1, int(mix.get("roam_min_turn", 2)) - 1)
    eligible = [i for i in range(n_sess) if n_turns[i] > first_roam]
    roamers = set(order.permutation(eligible)[:n_roam].tolist()) if len(nodes) > 1 else set()
    roam_at = {i: int(order.integers(first_roam, n_turns[i])) for i in sorted(roamers)}
    sys_tokens = int(mix.get("system_prompt_tokens", 0))
    system = prompt_text(rng, sys_tokens) if sys_tokens else ""

    sessions, k, t = [], 0, 0
    for i in range(n_sess):
        home = homes[i]
        at = roam_at.get(i, n_turns[i])
        away = nodes[(nodes.index(home) + 1) % len(nodes)]
        turns = []
        for j in range(n_turns[i]):
            body = prompt_text(rng, prompts[k])
            text = system + body if j == 0 else body
            turns.append(Turn(
                prompt=text, prompt_tokens=len(text), max_new=outputs[k],
                node=home if j < at else away,
                think_ms=0.0 if j == 0 else thinks[t],
                roam=j == at,
            ))
            k += 1
            t += j > 0
        sessions.append(Session(idx=i, start_ms=float(starts[i]), turns=tuple(turns)))
    return Plan(sessions=tuple(sessions))


def describe(plan: Plan) -> Dict[str, List[float]]:
    """Quartiles, 95th percentile and extremes of what the plan holds."""
    turns = [t for s in plan.sessions for t in s.turns]

    def q(xs):
        xs = np.asarray(xs, float)
        return [round(float(np.percentile(xs, p)), 1) for p in (0, 25, 50, 75, 95, 100)]

    out = {
        "turns_per_session": q([len(s.turns) for s in plan.sessions]),
        "prompt_tokens": q([t.prompt_tokens for t in turns]),
        "output_tokens": q([t.max_new for t in turns]),
        "think_ms": q([t.think_ms for t in turns if t.think_ms > 0] or [0.0]),
        "roam_turns": [float(sum(t.roam for t in turns))],
        "arrival_gap_ms": q(np.diff([s.start_ms for s in plan.sessions])),
    }
    return out


def max_context_tokens(plan: Plan, per_turn_overhead: int) -> int:
    """The longest model input plus output that any session of the plan
    reaches, with ``per_turn_overhead`` template tokens per turn."""
    worst = 0
    for s in plan.sessions:
        ctx = 0
        for t in s.turns:
            ctx += t.prompt_tokens + per_turn_overhead + t.max_new
            worst = max(worst, ctx)
    return worst


def ceil_pow2(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))
