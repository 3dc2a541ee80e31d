"""Closed-loop offline job: before every step the waiting queue is topped
up to ``backlog`` requests, so the server always has work queued and the
rate it completes is the rate it can sustain.

Lengths come in passes of ``segment`` requests, each holding the same
log-normal quantiles permuted by the seed, so every seed queues the same
work in another order.

The window opens on the job's steady state, not on its first wave: the
slots are preloaded with the requests a long-running job would hold at a
random moment (``preload``)."""
from __future__ import annotations

import numpy as np

from bench import lengths


class Generator:
    closed = True
    lead_s = 0.0

    def __init__(self, traffic: dict, seed: int, vocab: int, window_s: float):
        self.pool = int(traffic["segment"])
        self.backlog = int(traffic["backlog"])
        self.spec = traffic
        self.seed = seed
        self.prompts = lengths.Cycle(lengths.lognormal(traffic["prompt"], self.pool), lengths.rng(seed, 1))
        self.outputs = lengths.Cycle(lengths.lognormal(traffic["output"], self.pool), lengths.rng(seed, 2))
        self.tokens = lengths.rng(seed, 3)
        self.vocab = vocab

    def poll(self, t: float, waiting: int) -> list[tuple[float, list[int], int]]:
        """New requests ``(due, prompt, max_new_tokens)`` at time ``t``
        (seconds since the window opened) with ``waiting`` queued."""
        return [
            (t, lengths.prompt_tokens(self.tokens, self.prompts.next(), self.vocab), int(self.outputs.next()))
            for _ in range(max(self.backlog - waiting, 0))
        ]

    def next_due(self):
        return None

    def preload(self, slots: int, chunk: int) -> list[tuple[list[int], int]]:
        """``(prompt, max_new_tokens)`` of the requests in ``slots`` slots of
        the long-running job at a random moment.  A request of prompt p and
        output o holds its slot ceil(p / chunk) steps of prefill, the last of
        which samples its first token, then o - 1 decode steps.  A slot at a
        random moment holds a request drawn in proportion to those steps,
        at a uniform point of them; a request past its prefill is given as
        its prompt plus the tokens it has emitted, with the rest to come
        (one still prefilling starts its prefill afresh).

        Both draws are stratified over the joint law of the traffic's
        prompt and output quantiles: every seed preloads the same requests
        and the same points, paired in another order."""
        p = lengths.lognormal(self.spec["prompt"], self.pool)
        o = lengths.lognormal(self.spec["output"], self.pool)
        pp, oo = (a.ravel() for a in np.meshgrid(p, o))
        fill = -(-pp // chunk)
        hold = fill + oo - 1
        order = np.lexsort((oo, pp, hold))
        cum = np.cumsum(hold[order])
        picks = order[np.searchsorted(cum, (np.arange(slots) + 0.5) / slots * cum[-1])]
        point = lengths.rng(self.seed, 8).permutation((np.arange(slots) + 0.5) / slots)
        out = []
        for k, u in zip(picks, point):
            step = int(u * hold[k])
            done = step - fill[k] + 1 if step >= fill[k] else 0
            out.append((lengths.prompt_tokens(self.tokens, int(pp[k] + done), self.vocab), int(oo[k] - done)))
        return out
