"""The one general traffic generator. A traffic mix is a JSON file of parameters
under ``benchmark/traffic/``; nothing here knows a mix's name.

Schema (all lengths in tokens; see ``benchmark/README.md`` for an example):

    loop            "closed" (each client sends its next request when the last
                    ends) or "open" (arrivals on a schedule, whatever happened)
    clients         closed loop: number of clients
    rate_per_s      open loop: mean arrivals per second
    arrival         open loop: {"cv": c} inter-arrival gaps are gamma with
                    coefficient of variation c (1 = Poisson, 2+ = bursty)
    mix             list of {"weight", "prompt_tokens", "output_tokens"}; a
                    length is {"dist": "fixed", "value"} | {"dist": "uniform",
                    "low", "high"} | {"dist": "lognormal", "median", "sigma",
                    "low", "high"}
    sampling        {"temperature", "topp"}; temperature 0 is greedy. Sampled
                    requests get per-request seeds drawn from --seed.
    shared_prefix   {"share": s, "tokens": n}: a share s of requests starts
                    with the same n tokens (a system prompt)
    sessions        {"turns": [lo, hi], "think_s": t}: requests come in
                    sessions; a turn's prompt is the session so far (prompt +
                    what the program emitted) plus the turn's new tokens; the
                    next turn is due think_s after the previous one ended
    engine          optional engine options for this mix: "slots" and
                    "max_seq_len" replace the configuration's; any other key
                    goes to InferenceEngine, e.g. {"spec_lookup": 4}
    drain_limit_s   open loop: how long after the window an arrival may still
                    be awaited before it counts as failed
    sizes_seed      the seed of the sizes and gaps (see below)
    trace_slice     optional {"start_s": s, "seconds": n}: where a ``--trace 1``
                    run's traced slice lies in the window (``trace_slice``
                    below has the default). An open loop's slice is anchored
                    on arrivals (``slice_fault``): work begins at an arrival
                    whatever the program's speed, so a faster program cannot
                    empty such a slice, and it does empty one laid over the
                    tail of an earlier burst

Every ``--seed`` sees the SAME sequence of sizes and inter-arrival gaps, drawn
once from ``sizes_seed``; the seed draws the token ids, the per-request
sampling seeds and (in ``weights.py``) the weights. A seed that dealt the sizes
or the bursts anew would read as noise between runs: a window holds some tens
of requests, and on the chip a new order of the same sizes moved ``out_tok_s``
by 7% and the median TTFT by 12% where repeats of one order agree within 0.3%
and 5% (PERF.md, PR 24).
"""

from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass, field

import numpy as np


@dataclass
class PlannedRequest:
    idx: int
    due_s: float            # open loop: offset from the window's start; closed: 0
    client: int             # closed loop: which client sends it; open: -1
    new_tokens: list[int]   # the tokens this request adds (the prompt, unless a session turn)
    max_tokens: int
    temperature: float
    topp: float
    seed: int
    session: int = -1       # session id, or -1
    turn: int = 0


@dataclass
class Plan:
    loop: str
    clients: int
    requests: list[PlannedRequest]          # open: by due time; closed: per-client queues in order
    drain_limit_s: float
    think_s: float = 0.0
    engine: dict = field(default_factory=dict)
    max_context: int = 0                    # longest prompt + output any request can reach


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


TRACE_START_SHARE = 0.2   # absent "trace_slice": the slice starts this far into the window
TRACE_SECONDS = 8.0       # and lasts this long, or half the window if that is shorter
ANCHOR_ARRIVALS = 3       # an open loop's slice holds this many arrivals in its first half,
ANCHOR_WITHIN_S = 1.0     # the earliest of them due this soon after the slice starts


def trace_slice(mix: dict, seconds: float) -> tuple[float, float]:
    """(start_s, seconds) of the traced slice in a window of ``seconds``: the
    mix's ``trace_slice`` where it states one, else the default."""
    stated = mix.get("trace_slice")
    if stated is None:
        return TRACE_START_SHARE * seconds, min(TRACE_SECONDS, 0.5 * seconds)
    return float(stated["start_s"]), float(stated["seconds"])


def slice_fault(plan: "Plan", start_s: float, slice_s: float, seconds: float) -> str | None:
    """What is wrong with tracing [start_s, start_s + slice_s) of this plan's
    window, or ``None``. The slice lies inside the window. An open loop's
    slice is anchored on arrivals: its first half holds ANCHOR_ARRIVALS or
    more, the earliest due within ANCHOR_WITHIN_S of its start. A closed loop
    is loaded throughout and needs no anchor."""
    if not (slice_s > 0 and 0 <= start_s and start_s + slice_s <= seconds):
        return f"the slice {start_s:g}-{start_s + slice_s:g} s does not lie inside the window of {seconds:g} s"
    if plan.loop != "open":
        return None
    half = [r.due_s for r in plan.requests if start_s <= r.due_s < start_s + 0.5 * slice_s]
    if len(half) >= ANCHOR_ARRIVALS and min(half) - start_s <= ANCHOR_WITHIN_S:
        return None
    first = f"the earliest {min(half) - start_s:.2f} s in" if half else "none to be the earliest"
    return (f"the slice {start_s:g}-{start_s + slice_s:g} s is not anchored on arrivals: its first half holds "
            f"{len(half)}, {first} (an open loop needs {ANCHOR_ARRIVALS} or more, the earliest within "
            f"{ANCHOR_WITHIN_S:g} s); arrivals are due at " + ", ".join(f"{r.due_s:.2f}" for r in plan.requests) + " s")


STRATUM = 16   # lengths are dealt in blocks of this many, each block spread over the whole distribution


def _draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` lengths from the distribution, stratified: every block of STRATUM
    consecutive draws holds one value from each 1/STRATUM slice of the
    distribution, in random order. The distribution is the stated one; what
    goes is the luck of which sizes a window of some tens of requests happens
    to hold (with plain draws two seeds' windows differed by 7% in tokens/s)."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), dtype=np.int64)
    blocks = -(-n // STRATUM)
    strata = np.concatenate([rng.permutation(STRATUM) for _ in range(blocks)])[:n]
    u = (strata + rng.random(n)) / STRATUM
    if dist == "uniform":
        lo, hi = int(spec["low"]), int(spec["high"])
        return np.minimum(lo + np.floor(u * (hi - lo + 1)).astype(np.int64), hi)
    if dist == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(min(max(x, 1e-9), 1 - 1e-9)) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), int(spec["low"]), int(spec["high"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _upper(spec: dict) -> int:
    return int(spec["value"] if spec["dist"] == "fixed" else spec["high"])


def reachable_prompt_lengths(mix: dict) -> tuple[int, int]:
    """(shortest, longest) prompt the mix can send, for choosing warm-up shapes."""
    lows = [int(c["prompt_tokens"].get("low", c["prompt_tokens"].get("value", 1))) for c in mix["mix"]]
    highs = [_upper(c["prompt_tokens"]) for c in mix["mix"]]
    extra = int(mix.get("shared_prefix", {}).get("tokens", 0))
    turns = int(mix.get("sessions", {}).get("turns", [1, 1])[1])
    longest = extra + turns * (max(highs) + max(_upper(c["output_tokens"]) for c in mix["mix"]))
    return min(lows), longest if turns > 1 else extra + max(highs)


def plan(mix: dict, *, seed: int, seconds: float, vocab_size: int) -> Plan:
    """The requests of one run. Sizes and gaps come from ``sizes_seed`` and
    are the same for every seed; ``seed`` draws the ids."""
    sizes_rng = np.random.default_rng(int(mix.get("sizes_seed", 0)))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    loop = mix["loop"]
    sampling = mix.get("sampling", {})
    temperature, topp = float(sampling.get("temperature", 0.0)), float(sampling.get("topp", 0.9))
    comps = mix["mix"]
    weights = np.array([float(c.get("weight", 1.0)) for c in comps])
    weights = weights / weights.sum()

    if loop == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        clients = 0
    elif loop == "closed":
        clients = int(mix["clients"])
        # more than any client can finish: a request takes >= 8 decode steps
        n = clients * max(8, int(seconds * 4))
    else:
        raise ValueError(f"unknown loop {loop!r}")

    which = sizes_rng.choice(len(comps), size=n, p=weights)
    p_len = np.zeros(n, dtype=np.int64)
    o_len = np.zeros(n, dtype=np.int64)
    for i, c in enumerate(comps):
        sel = which == i
        p_len[sel] = _draw(c["prompt_tokens"], sizes_rng, int(sel.sum()))
        o_len[sel] = _draw(c["output_tokens"], sizes_rng, int(sel.sum()))
    if loop == "open":
        cv = float(mix.get("arrival", {}).get("cv", 1.0))
        gaps = sizes_rng.gamma(1.0 / cv ** 2, cv ** 2, size=n)
        gaps *= seconds / gaps.sum()            # every seed offers exactly n arrivals in the window
    else:
        gaps = np.zeros(n)

    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    sp = mix.get("shared_prefix", {})
    prefix = rng.integers(0, vocab_size, size=int(sp.get("tokens", 0))).tolist()
    has_prefix = rng.random(n) < float(sp.get("share", 0.0))
    sess = mix.get("sessions")
    reqs: list[PlannedRequest] = []
    session, turn, turns_left = -1, 0, 0
    for i in range(n):
        new = rng.integers(0, vocab_size, size=int(p_len[i])).tolist()
        if sess:
            if turns_left == 0:
                session, turn = session + 1, 0
                turns_left = int(rng.integers(sess["turns"][0], sess["turns"][1] + 1))
                if has_prefix[i]:
                    new = prefix + new
            else:
                turn += 1
            turns_left -= 1
        elif has_prefix[i]:
            new = prefix + new
        reqs.append(PlannedRequest(
            idx=i, due_s=float(due[i]),
            client=((session if sess else i) % clients if clients else -1),
            new_tokens=new, max_tokens=int(o_len[i]), temperature=temperature, topp=topp,
            seed=int(rng.integers(1, 2 ** 31)), session=session if sess else -1, turn=turn))
    _, longest = reachable_prompt_lengths(mix)
    return Plan(loop=loop, clients=clients, requests=reqs,
                drain_limit_s=float(mix.get("drain_limit_s", 30.0)),
                think_s=float((sess or {}).get("think_s", 0.0)), engine=dict(mix.get("engine", {})),
                max_context=longest + max(_upper(c["output_tokens"]) for c in comps))
