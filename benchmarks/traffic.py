"""The one traffic generator: a mix file of parameters + a seed -> the
requests of a run.  A new mix is a new data file under ``traffic/``, never
new code here.

A mix file holds:

``loop``           ``"closed"`` (``clients`` callers, each sends its next
                   request when its last one ended) or ``"open"`` (arrivals
                   on a schedule, whether or not earlier ones have ended)
``clients``        closed loop: the number of callers
``rate_rps``       open loop: arrivals per second; ``round(rate * seconds)``
                   arrivals in a run, the same count for every seed
``burst``          open loop, optional: ``{"share", "size", "within_s"}``:
                   that share of the arrivals comes in bursts of ``size``
                   inside ``within_s`` seconds, the rest evenly spaced
``system_tokens``  words in the system line every request starts with
``lengths``        the table of ``[prompt_tokens, output_tokens]`` pairs
``sharing``        ``"none"``: every prompt is fresh text
``order_seed``     optional: draws the order of the lengths and the phases of
                   arrivals and bursts in place of the run's seed, which then
                   draws the text alone

The table is cycled, so the work offered does not depend on the seed; the
seed sets the order, the text and the phase of steady arrivals and bursts.
Where which lengths meet in a burst or share the lanes decides a latency (a
scheduler under load), that order is itself most of the run-to-run spread:
such a mix fixes it with ``order_seed``, and every run offers the same
schedule with other text.
"""

from __future__ import annotations

import dataclasses
import random

from ggufgen import word

N_WORDS = 26 ** 3


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None        # open loop: offset from the window's start
    prompt_tokens: int         # target, as the server counts them
    max_tokens: int
    seed: int                  # draws the text


def _table(mix: dict) -> list[tuple[int, int]]:
    table = [(int(p), int(o)) for p, o in mix["lengths"]]
    if not table:
        raise ValueError(f"mix {mix.get('name')!r} has an empty length table")
    return table


def arrivals(mix: dict, seed: int, seconds: float) -> list[float]:
    """Open loop: the due times in [0, seconds), sorted.  Their count is
    ``round(rate_rps * seconds)`` whatever the seed."""
    rng = random.Random(int(mix.get("order_seed", seed)) * 7919 + 1)
    n = max(1, round(float(mix["rate_rps"]) * seconds))
    burst = mix.get("burst") or {}
    size = int(burst.get("size", 1))
    n_bursts = int(n * float(burst.get("share", 0.0))) // size if size > 1 else 0
    n_steady = n - n_bursts * size
    out = []
    if n_steady:
        gap = seconds / n_steady
        phase = rng.random() * gap
        out += [phase + i * gap for i in range(n_steady)]
    if n_bursts:
        gap = seconds / n_bursts
        within = float(burst["within_s"])
        phase = rng.random() * max(gap - within, 0.0)
        for b in range(n_bursts):
            start = phase + b * gap
            out += [min(start + j * within / (size - 1), seconds - 1e-3)
                    for j in range(size)]
    return sorted(out)


def requests(mix: dict, seed: int, seconds: float):
    """The run's requests, in the order they are to be sent.  Open loop: a
    list, one per arrival.  Closed loop: an endless iterator that all the
    callers draw from."""
    table = _table(mix)
    rng = random.Random(int(mix.get("order_seed", seed)) * 7919 + 2)
    text = random.Random(seed * 7919 + 3)
    if mix["loop"] == "open":
        due = arrivals(mix, seed, seconds)
        pairs = [table[i % len(table)] for i in range(len(due))]
        rng.shuffle(pairs)
        return [Request(i, t, p, o, text.getrandbits(32))
                for i, (t, (p, o)) in enumerate(zip(due, pairs))]
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    order = list(range(len(table)))
    rng.shuffle(order)

    def endless():
        i = 0
        while True:
            p, o = table[order[i % len(order)]]
            yield Request(i, None, p, o, text.getrandbits(32))
            i += 1

    return endless()


def system_line(mix: dict) -> str:
    """The same few words before every prompt (a persona line)."""
    return " ".join(word(i * 389) for i in range(int(mix["system_tokens"])))


def body(mix: dict, req: Request, overhead_tokens: int) -> dict:
    """The JSON body of ``req``.  ``overhead_tokens`` is what the chat
    template adds to a system line and a user message (measured once, in
    warm-up); every word is one token by the vocabulary's construction."""
    n_user = max(1, req.prompt_tokens - overhead_tokens
                 - int(mix["system_tokens"]))
    rng = random.Random(req.seed)
    text = " ".join(word(rng.randrange(N_WORDS)) for _ in range(n_user))
    return {
        "messages": [{"role": "system", "content": system_line(mix)},
                     {"role": "user", "content": text}],
        "stream": True,
        "stream_options": {"include_usage": True},
        "max_tokens": req.max_tokens,
    }
