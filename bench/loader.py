"""Seeded token batches and the loader that bounds the measured window.

`UniformTokens` draws every token uniformly from the vocabulary; a batch
is a pure function of (seed, index), so the reference regenerates
exactly the batches the program trained on, and every batch of a run
differs. Drawing a batch is one vectorized call (~0.1 ms at 4,096
tokens): a token loop in Python on a loader thread held the interpreter
lock in 5 ms slices and delayed the next step's dispatch by as much.

`WindowLoader` hands those batches to `TrainSession.run`. The first
`setup_steps` batches belong to set-up. Handing out the next one starts
the window; once `seconds` have passed since then, the loader stops at
the next step boundary, and `TrainLoop.run` ends cleanly on a dry loader.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np


class UniformTokens:
    """Seeded uniform tokens; batch `i` depends only on (seed, i)."""

    def __init__(self, vocab: int, seed: int):
        self.vocab = vocab
        self.seed = seed

    def batch(self, index: int, batch: int, seq_len: int) \
            -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1, index]))
        toks = rng.integers(0, self.vocab, size=(batch, seq_len + 1),
                            dtype=np.int32)
        return {"tokens": toks[:, :-1].copy(), "labels": toks[:, 1:].copy()}


class WindowLoader:
    """Iterator of batches that closes the window after `seconds`.

    `on_handout(i)` is called as batch `i` of the window is handed out
    (the tracer starts and stops there); `clock` is the host clock every
    window time is read from."""

    def __init__(self, tokens: UniformTokens, *, batch: int, seq_len: int,
                 setup_steps: int, seconds: float,
                 clock: Callable[[], float] = time.perf_counter,
                 on_handout: Optional[Callable[[int], None]] = None):
        self.tokens = tokens
        self.batch = batch
        self.seq_len = seq_len
        self.setup_steps = setup_steps
        self.seconds = seconds
        self.clock = clock
        self.on_handout = on_handout
        self.handed = 0
        self.window_start: Optional[float] = None
        self.window_batches = 0

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        i = self.handed
        if i >= self.setup_steps:
            now = self.clock()
            if self.window_start is None:
                self.window_start = now
            elif now - self.window_start >= self.seconds:
                raise StopIteration
            if self.on_handout is not None:
                self.on_handout(i - self.setup_steps)
            self.window_batches += 1
        self.handed += 1
        return self.tokens.batch(i, self.batch, self.seq_len)

    # the session checkpoints its loader's cursor
    def state_dict(self) -> Dict:
        return {"step": self.handed}

    def load_state_dict(self, state: Dict) -> None:
        raise NotImplementedError("the benchmark never resumes")


def setup_batches(tokens: UniformTokens, n: int, batch: int,
                  seq_len: int) -> List[Dict[str, np.ndarray]]:
    """The first `n` batches, as the program received them."""
    return [tokens.batch(i, batch, seq_len) for i in range(n)]
