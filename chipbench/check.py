"""Whether what the timed path produced is correct.

After the window, a sample of the streams it finished (drawn from the seed,
with the longest among them) is run through the plain reference, from the
same weights and frames.  Compared, each against the configuration's limit:

* ``logprob_err``: the largest |served log-prob - reference log-prob|;
* ``logprob_err_mean``: the mean of the same, over every frame and class;
* ``greedy_gap``: the widest gap by which the reference's log-prob of the
  symbol the engine put first lies below the reference's best, over every
  frame;
* ``symbols_wrong``: streams whose emitted CTC symbols differ from the
  greedy collapse of their own served log-probs (exact: limit 0);
* ``frames_missing``: output rows of sampled streams with no log-probs
  (exact); the model kind's ``outputs(frames)`` says how many rows a
  stream's frames yield.

A configuration's ``limits`` name the numbers compared; the others are
read and printed only.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


class Sample:
    """A reservoir of finished streams, drawn with ``rng``, plus the
    longest one seen.  Keeps each stream's frames, its served log-probs
    (copied) and its emitted symbols."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.seen = 0
        self.kept: list = []
        self.longest = None

    @staticmethod
    def _take(sess) -> tuple:
        symbols = sess.decoder.symbols if sess.decoder is not None else None
        return sess.frames, sess.full_log_probs(), symbols

    def offer(self, sessions) -> None:
        for sess in sessions:
            if self.longest is None or len(sess.frames) > len(
                    self.longest[0]):
                self.longest = self._take(sess)
            self.seen += 1
            if len(self.kept) < self.size:
                self.kept.append(self._take(sess))
            else:
                j = int(self.rng.integers(0, self.seen))
                if j < self.size:
                    self.kept[j] = self._take(sess)

    def streams(self) -> list:
        out = list(self.kept)
        if self.longest is not None and all(
                s[0] is not self.longest[0] for s in out):
            out.append(self.longest)
        return out


def collapse(best: np.ndarray, blank: int = 0) -> list:
    """Greedy CTC collapse of per-frame best symbols."""
    out, prev = [], -1
    for sym in best.tolist():
        if sym != blank and sym != prev:
            out.append(sym)
        prev = sym
    return out


def padded(streams: list, rows: int, frames: int, width: int) -> np.ndarray:
    """The streams' frames in one zero-padded (rows, frames, width) batch:
    the model is causal, so padding after a stream never reaches its
    outputs."""
    x = np.zeros((rows, frames, width), np.float32)
    for i, (f, _, _) in enumerate(streams):
        x[i, :len(f)] = f
    return x


def reference_log_probs(reference, config: dict, weights: dict,
                        streams: list, rows: int, frames: int,
                        dtype=jnp.float32) -> np.ndarray:
    """The reference's log-probs of ``streams`` at the configuration's
    precision of products (with ``dtype=bfloat16``: its control), in one
    padded batch of ``rows`` x ``frames``."""
    x = jnp.asarray(padded(streams, rows, frames, config['model']['inputs']))
    return np.asarray(reference.log_probs(weights, x,
                                          products=config['products'],
                                          dtype=dtype))


def numbers(streams: list, ref_lp: np.ndarray, outputs) -> dict:
    """The compared numbers of served ``streams`` against reference
    log-probs ``ref_lp`` (rows in the same order); ``outputs(frames)`` is
    the number of output rows a stream of ``frames`` input frames yields."""
    err = gap = total = 0.0
    wrong = missing = count = 0
    for i, (f, lp, symbols) in enumerate(streams):
        due = outputs(len(f))
        n = min(due, len(lp))
        missing += due - n
        if n == 0:
            continue
        r = ref_lp[i, :n]
        diff = np.abs(lp[:n] - r)
        err = max(err, float(diff.max()))
        total += float(diff.sum(dtype=np.float64))
        count += diff.size
        best = lp[:n].argmax(-1)
        gap = max(gap, float((r.max(-1) - np.take_along_axis(
            r, best[:, None], -1)[:, 0]).max()))
        if symbols is not None and list(symbols) != collapse(best):
            wrong += 1
    return {'logprob_err': err,
            'logprob_err_mean': total / count if count else 0.0,
            'greedy_gap': gap, 'symbols_wrong': wrong,
            'frames_missing': missing}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {'value', 'limit'}}) over the limited numbers."""
    checks = {k: {'value': values[k], 'limit': limits[k]} for k in limits}
    return all(c['value'] <= c['limit'] for c in checks.values()), checks
