"""Open loop: independent users send requests at ``rate_per_s`` on average,
whether or not earlier requests have finished.

Arrivals are a Poisson process.  Inside the window it is conditioned on its
count: ``round(rate * window)`` requests at uniform random times (the
Poisson process given its count), so every seed has the same number due and
bursts at every scale below the window.  Before the window (``lead_s``, so
that it opens on a loaded server) and after it (while the window's requests
drain) the gaps are independent exponentials.

The window's prompt and output lengths are the log-normal's quantiles at
``(i + 0.5) / n``, permuted by the seed: every seed sends the same lengths
in another order.  Requests outside the window draw from the same quantiles."""
from __future__ import annotations

import numpy as np

from bench import lengths


class Generator:
    closed = False

    def __init__(self, traffic: dict, seed: int, vocab: int, window_s: float):
        self.rate = float(traffic["rate_per_s"])
        self.lead_s = float(traffic.get("lead_s", 0.0))
        self.window_s = float(window_s)
        n = round(self.rate * self.window_s)
        times = lengths.rng(seed, 4)
        self.gaps = lengths.rng(seed, 7)
        lead = -self.lead_s + np.cumsum(self.gaps.exponential(1.0 / self.rate, 4 * round(self.rate * self.lead_s) + 8))
        inside = np.sort(times.uniform(0.0, self.window_s, n))
        self.dues = [float(t) for t in lead[lead < 0.0]] + inside.tolist()
        self.n_lead = len(self.dues) - n
        self.sizes = [(int(p), int(o)) for p, o in zip(
            lengths.rng(seed, 1).permutation(lengths.lognormal(traffic["prompt"], n)),
            lengths.rng(seed, 2).permutation(lengths.lognormal(traffic["output"], n)))]
        self.prompts = lengths.Cycle(lengths.lognormal(traffic["prompt"], n), lengths.rng(seed, 5))
        self.outputs = lengths.Cycle(lengths.lognormal(traffic["output"], n), lengths.rng(seed, 6))
        self.tokens = lengths.rng(seed, 3)
        self.vocab = vocab
        self.i = 0

    def next_due(self) -> float:
        while self.i >= len(self.dues):  # after the window: Poisson again
            last = max(self.dues[-1] if self.dues else 0.0, self.window_s)
            self.dues.append(last + float(self.gaps.exponential(1.0 / self.rate)))
        return self.dues[self.i]

    def _size(self) -> tuple[int, int]:
        k = self.i - self.n_lead
        if 0 <= k < len(self.sizes):
            return self.sizes[k]
        return int(self.prompts.next()), int(self.outputs.next())

    def poll(self, t: float, waiting: int) -> list[tuple[float, list[int], int]]:
        """Requests ``(due, prompt, max_new_tokens)`` due by ``t`` (seconds
        from the window's opening; negative before it)."""
        out = []
        while self.next_due() <= t:
            p, o = self._size()
            out.append((self.next_due(), lengths.prompt_tokens(self.tokens, p, self.vocab), o))
            self.i += 1
        return out

    def preload(self, slots: int, chunk: int) -> list:
        return []
