"""The one traffic generator: every mix is a data file of its parameters.

Three arrival processes, chosen by the mix's ``kind``:

* ``poisson``  independent requests, open loop at the cell's ``rate_per_s``;
* ``backlog``  offline batch: at least ``waiting_per_slot`` x slots requests
  wait at all times;
* ``sessions`` multi-turn chats arriving open loop at ``rate_per_s``: each
  turn's prompt is a shared system prompt, the session's history (its
  earlier messages and the answers actually served) and a new message, and
  the next turn is due a think time after the answer.

Lengths, gaps and think times are not sampled independently: each phase of
a run (the lead before the window, the window, the drain after it) takes
the quantiles ``(i + 1/2) / n`` of its distribution in an order drawn from
the seed.  Every seed therefore puts the same set of sizes and the same
total of gaps into the window, in another order, so seeds differ in
arrangement and not in the amount of work.  Token ids come from the seed.

All times are seconds from the start of traffic; the window opens at
``lead_s``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

DRAIN_S = 60.0  # arrivals keep coming while the window's answers finish
_NORMAL = NormalDist()


@dataclasses.dataclass
class Req:
    prompt: np.ndarray     # (T,) int32
    max_new: int
    due: float             # seconds from the start of traffic
    session: int = -1      # sessions only: which session asked


def _quantiles(n: int, ppf, rng: np.random.Generator) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(np.asarray([ppf(x) for x in q], np.float64))


def _lognormal(p: dict):
    """ppf of a lognormal given by median and sigma, clipped to [min, max]."""
    med, sig, lo, hi = p["median"], p["sigma"], p["min"], p["max"]

    def ppf(q: float) -> int:
        return int(min(hi, max(lo, round(med * math.exp(sig * _NORMAL.inv_cdf(q))))))

    return ppf


def _exponential(mean: float):
    return lambda q: -math.log1p(-q) * mean


def _arrivals(rate: float, start: float, dur: float, rng) -> np.ndarray:
    """``round(rate * dur)`` arrival times in ``[start, start + dur)`` whose
    gaps are exponential quantiles scaled to fill the phase exactly."""
    n = max(1, round(rate * dur))
    gaps = _quantiles(n, _exponential(1.0 / rate), rng)
    gaps *= dur / gaps.sum()
    return start + np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def _phases(lead: float, seconds: float):
    return ((0.0, lead), (lead, seconds), (lead + seconds, DRAIN_S))


class Traffic:
    """Requests of one mix for one seed; the run loop polls :meth:`due`."""

    def __init__(self, mix: dict, cell: dict, cfg: dict, seed: int, seconds: float):
        self.kind = mix["kind"]
        self.lead = float(mix.get("lead_s", 0.0))
        self.seconds = float(seconds)
        serve = cfg["serve"]
        self.max_len = int(serve["max_len"])
        self.vocab = int(cfg["model"]["vocab"])
        self.rng = np.random.default_rng(seed)
        self.mix = mix
        self._queue: list[Req] = []        # open loop: sorted by due
        self._next = 0
        if self.kind == "poisson":
            self._poisson(float(cell["rate_per_s"]))
        elif self.kind == "backlog":
            self.target = int(mix["waiting_per_slot"] * serve["slots"])
            self._lens: list[tuple[int, int]] = []
        elif self.kind == "sessions":
            self._sessions(float(cell["rate_per_s"]))
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}")

    # ------------------------------------------------------------ sizes --
    @property
    def max_prompt(self) -> int:
        """The longest prompt this mix can send (warm-up covers up to it)."""
        if self.kind == "sessions":
            return self.max_len - self.mix["answer"]["min"]
        return min(self.mix["prompt"]["max"], self.max_len - 1)

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n).astype(np.int32)

    def _budget(self, plen: int, out: int) -> int:
        return max(1, min(out, self.max_len - plen))

    # ------------------------------------------------------------ poisson --
    def _poisson(self, rate: float) -> None:
        pp, po = _lognormal(self.mix["prompt"]), _lognormal(self.mix["output"])
        for start, dur in _phases(self.lead, self.seconds):
            due = _arrivals(rate, start, dur, self.rng)
            plens = _quantiles(len(due), pp, self.rng)
            outs = _quantiles(len(due), po, self.rng)
            for t, p, o in zip(due, plens, outs):
                p = min(int(p), self.max_len - 1)
                self._queue.append(Req(self._tokens(p), self._budget(p, int(o)), float(t)))

    # ------------------------------------------------------------ backlog --
    def _backlog(self, n: int, now: float) -> list[Req]:
        out = []
        for _ in range(n):
            if not self._lens:
                pp = _lognormal(self.mix["prompt"])
                po = _lognormal(self.mix["output"])
                k = 1024
                self._lens = list(zip(
                    _quantiles(k, pp, self.rng).astype(int).tolist(),
                    _quantiles(k, po, self.rng).astype(int).tolist(),
                ))
            p, o = self._lens.pop()
            p = min(p, self.max_len - 1)
            out.append(Req(self._tokens(p), self._budget(p, o), now))
        return out

    # ----------------------------------------------------------- sessions --
    def _sessions(self, rate: float) -> None:
        m = self.mix
        n_sys = int(m["system_prompts"])
        self.system = [self._tokens(int(m["system_len"])) for _ in range(n_sys)]
        w = np.arange(1, n_sys + 1, dtype=np.float64) ** -float(m["zipf"])
        cdf = np.cumsum(w / w.sum())

        def pick(q: float) -> int:
            return int(np.searchsorted(cdf, q))

        lo, hi = m["turns"]
        pu, pa = _lognormal(m["user"]), _lognormal(m["answer"])
        think = _exponential(float(m["think_mean_s"]))
        self._sess: list[dict] = []
        for start, dur in _phases(self.lead, self.seconds):
            due = _arrivals(rate, start, dur, self.rng)
            n = len(due)
            sys_ids = _quantiles(n, pick, self.rng).astype(int)
            turns = _quantiles(n, lambda q: lo + min(hi - lo, int(q * (hi - lo + 1))), self.rng)
            users = iter(_quantiles(n * hi, pu, self.rng).astype(int))
            answers = iter(_quantiles(n * hi, pa, self.rng).astype(int))
            thinks = iter(_quantiles(n * hi, think, self.rng))
            for t, s, k in zip(due, sys_ids, turns):
                sid = len(self._sess)
                plan = [(next(users), next(answers), next(thinks)) for _ in range(int(k))]
                self._sess.append({"history": self.system[s], "plan": plan})
                self._turn(sid, float(t))
        self._queue.sort(key=lambda r: r.due)

    def _turn(self, sid: int, due: float) -> None:
        """Queue the session's next turn, unless it is done or would not fit."""
        sess = self._sess[sid]
        if not sess["plan"]:
            return
        user, answer, think = sess["plan"].pop(0)
        prompt = np.concatenate([sess["history"], self._tokens(int(user))])
        if len(prompt) + int(answer) > self.max_len:
            sess["plan"].clear()
            return
        sess["history"] = prompt
        sess["think"] = float(think)
        req = Req(prompt, int(answer), due, sid)
        i = len(self._queue)
        while i > self._next and self._queue[i - 1].due > due:
            i -= 1
        self._queue.insert(i, req)

    def finished(self, req: Req, answer: np.ndarray, now: float) -> None:
        """A session's turn was answered at ``now``: its next turn is due a
        think time later, with the served answer in its history."""
        if req.session < 0:
            return
        sess = self._sess[req.session]
        sess["history"] = np.concatenate([sess["history"], np.asarray(answer, np.int32)])
        self._turn(req.session, now + sess.get("think", 0.0))

    # ------------------------------------------------------------- polling --
    def due(self, now: float, waiting: int) -> list[Req]:
        """Requests due at ``now`` (seconds from the start of traffic), given
        how many of the submitted ones still wait."""
        if self.kind == "backlog":
            return self._backlog(max(0, self.target - waiting), now)
        out = []
        while self._next < len(self._queue) and self._queue[self._next].due <= now:
            out.append(self._queue[self._next])
            self._next += 1
        return out

    def next_due(self) -> float | None:
        if self.kind == "backlog" or self._next >= len(self._queue):
            return None
        return self._queue[self._next].due
