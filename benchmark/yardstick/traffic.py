"""One general traffic generator: a mix is a data file, a schedule is what this
module makes of it from the seed. Equal seeds give equal schedules.

A mix names a ``loop`` (``closed`` with ``clients``, or ``open`` with
``rate_per_s``), the server options, the ``latent`` shape and the per-request
``draws`` (noise seed, prompt text). An open loop has a fixed count of
requests for the window, ``round(rate_per_s * seconds)``, at exponential gaps
rescaled to span it: every seed offers the same amount of work at the same
mean rate, in another order."""

from __future__ import annotations

import copy
import dataclasses
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float | None  # offset from window start; None in a closed loop
    noise_seed: int
    positive: str
    negative: str


def _words() -> list[str]:
    with open(os.path.join(HERE, "..", "traffic", "words.txt")) as f:
        return [w for w in f.read().split() if w]


class Schedule:
    """Request ``i`` of a mix under a seed, on demand (a closed loop does not
    know beforehand how many requests its window will hold)."""

    def __init__(self, mix: dict, seed: int, seconds: float):
        self.mix, self.seed, self.seconds = mix, int(seed), float(seconds)
        self.loop = mix["loop"]
        draws = mix["draws"]
        self._prompt = draws["prompt"]
        self._negative = draws.get("negative", "")
        self._words = _words() if self._prompt["kind"] != "fixed" else []
        self.due: list[float] | None = None
        if self.loop == "open":
            n = max(1, round(mix["rate_per_s"] * self.seconds))
            rng = np.random.default_rng([self.seed, 10])
            gaps = rng.exponential(1.0, n)
            at = np.cumsum(gaps) - gaps[0]
            # n arrivals over [0, seconds): the last leaves a mean gap of room
            self.due = list(at * (self.seconds * (n - 1) / n) / max(at[-1], 1e-9))
        elif self.loop != "closed":
            raise ValueError(f"unknown loop kind {self.loop!r}")

    def count(self) -> int | None:
        return None if self.due is None else len(self.due)

    def request(self, i: int) -> Request:
        rng = np.random.default_rng([self.seed, 11, i])
        noise_seed = int(rng.integers(0, 2 ** 48))
        kind = self._prompt["kind"]
        if kind == "fixed":
            text = self._prompt["text"]
        elif kind == "unique":
            k = int(self._prompt.get("words", 8))
            text = " ".join(self._words[j] for j in
                            rng.integers(0, len(self._words), k))
        elif kind == "zipf":
            n = int(self._prompt["texts"])
            w = 1.0 / np.arange(1, n + 1) ** float(self._prompt["s"])
            pick = int(rng.choice(n, p=w / w.sum()))
            trng = np.random.default_rng([self.seed, 12, pick])
            k = int(self._prompt.get("words", 8))
            text = " ".join(self._words[j] for j in
                            trng.integers(0, len(self._words), k))
        else:
            raise ValueError(f"unknown prompt kind {kind!r}")
        return Request(i, None if self.due is None else float(self.due[i]),
                       noise_seed, text, self._negative)


def fill_graph(template: dict, mix: dict, req: Request) -> dict:
    """The API-format graph of one request: the template's ``graph`` with the
    request's draws and the mix's latent shape written into its ``slots``."""
    graph = copy.deepcopy(template["graph"])
    values = {"seed": req.noise_seed, "positive": req.positive,
              "negative": req.negative, **mix.get("latent", {})}
    for name, paths in template["slots"].items():
        if name not in values:
            continue
        for node, key in paths:
            graph[node]["inputs"][key] = values[name]
    return graph
